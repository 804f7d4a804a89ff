"""Real OS-process cluster demo: 4 skvbc replicas over UDP localhost +
the TesterClient workload binary driving them.

This is the reference's tests/simpleTest/scripts flow
(testReplicasAndClient.sh): real processes, real sockets, one command.
"""
import json
import os
import random
import subprocess
import sys
import time

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, REPO)


def main() -> None:
    base_port = random.randint(20000, 50000)
    # replicas run the cpu crypto backend (skvbc_replica's default) and
    # never start JAX, so n processes need no chip
    env = dict(os.environ, PYTHONPATH=REPO)
    procs = []
    print(f"spawning 4 replica processes (base port {base_port})...")
    for r in range(4):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "tpubft.apps.skvbc_replica",
             "--replica", str(r), "--f", "1",
             "--base-port", str(base_port),
             "--metrics-port", str(base_port + 1000 + r)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
    try:
        time.sleep(2.0)
        print("running the TesterClient workload...")
        out = subprocess.run(
            [sys.executable, "-m", "tpubft.apps.tester_client",
             "--f", "1", "--base-port", str(base_port),
             "--ops", "60", "--concurrency", "2"],
            env=env, capture_output=True, text=True, timeout=120)
        lines = out.stdout.strip().splitlines()
        if not lines:
            raise SystemExit(
                f"tester_client produced no output (rc={out.returncode}):\n"
                f"{out.stderr.strip()[-2000:]}")
        summary = json.loads(lines[-1])
        print(json.dumps(summary, indent=2))
        assert summary["ok"], "workload checks failed"
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
    print("done.")


if __name__ == "__main__":
    main()
