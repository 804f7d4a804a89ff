"""BftTestNetwork — the system-test harness running REAL replica
processes.

Rebuild of the reference's Apollo core (/root/reference/tests/apollo/
util/bft.py:233 BftTestNetwork): each replica is an OS subprocess of the
actual SKVBC tester replica (subprocess.Popen, bft.py:818), driven from
the test through real UDP clients, observed through each replica's UDP
metrics server (bft_metrics.py), and fault-injected by killing/restarting
processes and by pausing them with SIGSTOP/SIGCONT (the portable stand-in
for Apollo's iptables partitioning — a stopped process neither sends nor
receives, which is exactly a partition from the cluster's viewpoint).
"""
from __future__ import annotations

import json
import os
import random
import signal
import socket
import subprocess
import sys
import time
import weakref
from typing import Dict, List, Optional

from tpubft.apps.simple_test import endpoint_table
from tpubft.apps.skvbc import SkvbcClient
from tpubft.bftclient import BftClient, ClientConfig
from tpubft.comm import CommConfig, PlainUdpCommunication
from tpubft.consensus.keys import ClusterKeys
from tpubft.utils.config import ReplicaConfig

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


class MetricsClient:
    """Polls a replica's UDP metrics server (reference bft_metrics.py)."""

    def __init__(self, port: int, host: str = "127.0.0.1") -> None:
        self.addr = (host, port)

    def snapshot(self, timeout: float = 1.0) -> Optional[dict]:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.settimeout(timeout)
        try:
            s.sendto(b"metrics", self.addr)
            data, _ = s.recvfrom(1 << 20)
            return json.loads(data.decode())
        except (OSError, json.JSONDecodeError):
            return None
        finally:
            s.close()

    def get(self, component: str, kind: str, name: str,
            timeout: float = 1.0):
        snap = self.snapshot(timeout)
        if snap is None:
            return None
        try:
            return snap["components"][component][kind][name]
        except KeyError:
            return None


class BftTestNetwork:
    def __init__(self, f: int = 1, c: int = 0, num_clients: int = 4,
                 num_ro: int = 0,
                 base_port: Optional[int] = None,
                 db_dir: Optional[str] = None,
                 seed: str = "apollo-net",
                 view_change_timeout_ms: int = 3000,
                 crypto_backend: str = "cpu",
                 pre_execution: bool = False,
                 checkpoint_window: int = 150,
                 work_window: int = 300,
                 transport: str = "udp",
                 threshold_scheme: str = "multisig-ed25519",
                 client_sig_scheme: str = "ed25519",
                 device_min_verify_batch: Optional[int] = None,
                 merkle: bool = False,
                 cfg_overrides: Optional[dict] = None) -> None:
        self.f, self.c = f, c
        self.n = 3 * f + 2 * c + 1
        self.num_ro = num_ro
        self.num_clients = num_clients
        self.seed = seed
        self.base_port = base_port or random.randint(20000, 50000)
        self.metrics_base = self.base_port + 1000
        self.fault_base = self.base_port + 2000
        self.trs_base = self.base_port + 3000   # thin-replica servers
        self.diag_base = self.base_port + 4000  # diagnostics admin servers
        self.db_dir = db_dir
        self.view_change_timeout_ms = view_change_timeout_ms
        self.crypto_backend = crypto_backend
        self.pre_execution = pre_execution
        self.checkpoint_window = checkpoint_window
        self.work_window = work_window
        self.transport = transport
        self.threshold_scheme = threshold_scheme
        self.client_sig_scheme = client_sig_scheme
        self.device_min_verify_batch = device_min_verify_batch
        self.merkle = merkle     # BLOCK_MERKLE skvbc state (provable
        # reads for the thin-replica tier)
        # arbitrary ReplicaConfig fields, forwarded to every replica
        # process as --config-override FIELD=VALUE
        self.cfg_overrides = dict(cfg_overrides or {})
        self.certs_dir = None
        if transport in ("tls", "tls-mux"):
            # pinned-cert material for every principal (replicas +
            # clients + operator), like keygen --tls-certs
            assert db_dir, "TLS transport needs db_dir for cert material"
            from tpubft.comm.tls import generate_tls_material
            from tpubft.consensus.replicas_info import ReplicasInfo
            cfg = ReplicaConfig(f_val=f, c_val=c,
                                num_of_client_proxies=num_clients)
            op_id = ReplicasInfo.from_config(cfg).operator_id
            ids = (list(range(self.n))
                   + list(range(self.n, self.n + num_clients)) + [op_id])
            self.certs_dir = os.path.join(db_dir, "tls")
            os.makedirs(self.certs_dir, exist_ok=True)
            generate_tls_material(self.certs_dir, ids, seed=None)
        self.procs: Dict[int, subprocess.Popen] = {}
        self.paused: set = set()
        self._clients: Dict[int, BftClient] = {}
        # teardown guarantee: even when a red assertion (or a crashed
        # test runner) skips __exit__/stop_all, no SIGSTOP'd or live
        # replica subprocess may outlive this harness — a stopped orphan
        # holds its ports and poisons every later test on the host. The
        # finalizer fires at GC or interpreter exit and must not hold a
        # reference to self (it would never fire), so it closes over the
        # mutable dicts only.
        self._finalizer = weakref.finalize(
            self, BftTestNetwork._reap_procs, self.procs, self.paused)

    @staticmethod
    def _reap_procs(procs: Dict[int, subprocess.Popen],
                    paused: set) -> None:
        """Last-resort reaper: SIGCONT anything stopped, SIGKILL, reap.
        (SIGKILL does kill a stopped process, but the SIGCONT keeps the
        behavior uniform with stop_all's graceful path and unsticks any
        descendant blocked on the stopped parent.)"""
        for r, p in list(procs.items()):
            try:
                if p.poll() is None:
                    if r in paused:
                        p.send_signal(signal.SIGCONT)
                    p.kill()
            except OSError:
                pass
        for p in list(procs.values()):
            try:
                p.wait(timeout=5)
            except (subprocess.TimeoutExpired, OSError):
                pass
        paused.clear()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start_all(self, timeout: float = 120.0) -> "BftTestNetwork":
        # 120s: n replica processes pay CONCURRENT contended jax imports
        # (~10-20s each when the 1-core host is busy) — 30s and 60s both
        # flaked under background load; boot time is not what any of
        # these scenarios measure
        from tpubft.crypto.backend import check_process_fanout
        check_process_fanout(self.crypto_backend, self.n)
        try:
            for r in range(self.n):
                self.start_replica(r)
            self.wait_for_replicas_up(timeout=timeout)
        except BaseException:
            # a failed startup must not leak live replica processes (a
            # 31-process orphan herd from one failed start poisons every
            # later measurement on the host)
            self.stop_all()
            raise
        return self

    def start_replica(self, r: int,
                      extra_args: Optional[List[str]] = None,
                      extra_env: Optional[Dict[str, str]] = None) -> None:
        assert r not in self.procs or self.procs[r].poll() is not None
        # children inherit the parent's JAX environment (platform and
        # compile-cache placement): the tests export JAX_PLATFORMS=cpu,
        # so `crypto_backend="tpu"` there is the XLA-CPU rehearsal, and
        # a replica asked for the device backend is never silently put
        # on the CPU by its launcher
        env = dict(os.environ, PYTHONPATH=_REPO_ROOT, **(extra_env or {}))
        args = [sys.executable, "-m", "tpubft.apps.skvbc_replica",
                "--replica", str(r), "--f", str(self.f), "--c", str(self.c),
                "--ro", str(self.num_ro),
                "--clients", str(self.num_clients),
                "--base-port", str(self.base_port),
                "--metrics-port", str(self.metrics_base + r),
                "--seed", self.seed,
                "--view-change-timeout-ms",
                str(self.view_change_timeout_ms),
                "--fault-port", str(self.fault_base + r),
                "--trs-port", str(self.trs_base + r),
                "--diag-port", str(self.diag_base + r),
                "--crypto-backend", self.crypto_backend,
                "--checkpoint-window", str(self.checkpoint_window),
                "--work-window", str(self.work_window),
                "--threshold-scheme", self.threshold_scheme,
                "--client-sig-scheme", self.client_sig_scheme,
                "--transport", self.transport] + (extra_args or [])
        if self.device_min_verify_batch is not None:
            args += ["--device-min-verify-batch",
                     str(self.device_min_verify_batch)]
        for k, v in self.cfg_overrides.items():
            args += ["--config-override", f"{k}={v}"]
        if self.certs_dir:
            args += ["--certs-dir", self.certs_dir]
        if self.pre_execution:
            args += ["--pre-execution"]
        if self.merkle:
            args += ["--merkle"]
        if self.db_dir:
            args += ["--db-dir", self.db_dir]
        # per-replica log files (Apollo keeps logs under
        # build/tests/apollo/logs — CMakeLists.txt:27)
        if self.db_dir:
            log = open(os.path.join(self.db_dir,
                                    f"replica-{r}.log"), "ab")
            out = err = log
        else:
            out = err = subprocess.DEVNULL
        self.procs[r] = subprocess.Popen(args, env=env, stdout=out,
                                         stderr=err)
        if out is not subprocess.DEVNULL:
            out.close()                   # child keeps its own fd

    def start_ro_replica(self, idx: int = 0,
                         extra_args: Optional[List[str]] = None,
                         extra_env: Optional[Dict[str, str]] = None) -> int:
        """Spawn a read-only replica process (id n+idx) — the archival
        follower (reference RO TesterReplica variant). Returns its id."""
        rid = self.n + idx
        assert idx < self.num_ro, "construct the network with num_ro"
        env = dict(os.environ, PYTHONPATH=_REPO_ROOT, **(extra_env or {}))
        args = [sys.executable, "-m", "tpubft.apps.ro_replica",
                "--replica", str(rid), "--f", str(self.f),
                "--c", str(self.c), "--ro", str(self.num_ro),
                "--clients", str(self.num_clients),
                "--base-port", str(self.base_port),
                "--metrics-port", str(self.metrics_base + rid),
                "--seed", self.seed,
                "--checkpoint-window", str(self.checkpoint_window),
                "--threshold-scheme", self.threshold_scheme,
                "--client-sig-scheme", self.client_sig_scheme,
                "--transport", self.transport] + (extra_args or [])
        if self.certs_dir:
            args += ["--certs-dir", self.certs_dir]
        if self.db_dir:
            log = open(os.path.join(self.db_dir, f"ro-{rid}.log"), "ab")
            out = err = log
        else:
            out = err = subprocess.DEVNULL
        self.procs[rid] = subprocess.Popen(args, env=env, stdout=out,
                                           stderr=err)
        if out is not subprocess.DEVNULL:
            out.close()
        return rid

    def stop_all(self) -> None:
        for r, p in list(self.procs.items()):
            if p.poll() is None:
                # SIGCONT first: a SIGTERM delivered to a stopped process
                # stays pending until it resumes — without this, every
                # paused replica rides the 5s escalation below
                if r in self.paused:
                    p.send_signal(signal.SIGCONT)
                p.send_signal(signal.SIGTERM)
        for p in list(self.procs.values()):
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                try:
                    p.wait(timeout=5)   # actually reap — no zombies
                except subprocess.TimeoutExpired:
                    pass
        self.paused.clear()
        for cl in self._clients.values():
            try:
                cl.stop()
            except Exception:  # noqa: BLE001 — teardown must not raise
                pass

    # ------------------------------------------------------------------
    # fault injection (Apollo kill/restart + partition analogs)
    # ------------------------------------------------------------------
    def kill_replica(self, r: int) -> None:
        """Hard crash (SIGKILL) — Apollo bft.py stop_replica."""
        p = self.procs[r]
        if p.poll() is None:
            p.send_signal(signal.SIGKILL)
            p.wait()
        self.paused.discard(r)       # a dead process is no longer paused

    def wait_exit(self, r: int, timeout: float = 30.0) -> int:
        """Block until replica r's process exits on its own (crashpoint
        drills assert the exit CODE to prove the seam fired)."""
        return self.procs[r].wait(timeout=timeout)

    def restart_replica(self, r: int,
                        extra_args: Optional[List[str]] = None,
                        extra_env: Optional[Dict[str, str]] = None) -> None:
        self.kill_replica(r)
        self.start_replica(r, extra_args=extra_args, extra_env=extra_env)

    def pause_replica(self, r: int) -> None:
        """SIGSTOP: the replica is partitioned from the cluster (alive,
        silent) — analog of Apollo's iptables isolation."""
        self.procs[r].send_signal(signal.SIGSTOP)
        self.paused.add(r)

    def resume_replica(self, r: int) -> None:
        self.procs[r].send_signal(signal.SIGCONT)
        self.paused.discard(r)

    # ---- per-link faults (Apollo bft_network_partitioning.py analog,
    # via the in-process FaultControlServer instead of iptables) ----
    def drop_link(self, frm: int, to: int) -> None:
        """Asymmetric partition: frm stops SENDING to `to` (traffic
        to→frm still flows)."""
        from tpubft.testing.faults import fault_command
        state = fault_command(self.fault_base + frm, cmd="get") or {}
        drops = set(state.get("drop_to", [])) | {to}
        assert fault_command(self.fault_base + frm, cmd="set",
                             drop_to=sorted(drops)) is not None

    def isolate_replica(self, r: int, peers: Optional[List[int]] = None
                        ) -> None:
        """Symmetric isolation of r from `peers` (default: all replicas)
        without stopping the process — unlike SIGSTOP the replica keeps
        running (timers fire, complaints accumulate)."""
        from tpubft.testing.faults import fault_command
        others = [p for p in (peers if peers is not None
                              else range(self.n)) if p != r]
        assert fault_command(self.fault_base + r, cmd="set",
                             drop_to=others, drop_from=others) is not None

    def deafen_replica(self, r: int) -> None:
        """The classic view-change liveness trap (reference apollo
        partitioning's one-direction iptables DROP): replica r keeps
        SENDING — status beacons, PrePrepares, shares all flow out, so it
        looks alive to naive failure detection — but receives NOTHING
        (peers, clients, operator). If r is the primary, the cluster must
        view-change away despite the heartbeats."""
        from tpubft.consensus.replicas_info import ReplicasInfo
        from tpubft.testing.faults import fault_command
        op_id = ReplicasInfo.from_config(self._node_cfg()).operator_id
        everyone = [i for i in
                    list(range(self.n + self.num_ro + self.num_clients))
                    + [op_id] if i != r]
        assert fault_command(self.fault_base + r, cmd="set",
                             drop_from=everyone) is not None

    def set_loss(self, r: int, loss: float) -> None:
        """Uniform probabilistic message loss at replica r."""
        from tpubft.testing.faults import fault_command
        assert fault_command(self.fault_base + r, cmd="set",
                             loss=loss) is not None

    def set_delay(self, r: int, delay_ms: float,
                  jitter_ms: float = 0.0) -> None:
        """Latency shaping at replica r: every outbound message is held
        delay_ms ± jitter_ms before hitting the wire (the Apollo
        bft_network_traffic_control.py tc/netem role)."""
        from tpubft.testing.faults import fault_command
        assert fault_command(self.fault_base + r, cmd="set",
                             delay_ms=delay_ms,
                             jitter_ms=jitter_ms) is not None

    def heal(self, r: Optional[int] = None) -> None:
        """Clear all injected faults (for one replica or all)."""
        from tpubft.testing.faults import fault_command
        for rr in ([r] if r is not None else list(range(self.n))):
            fault_command(self.fault_base + rr, cmd="clear")

    # ------------------------------------------------------------------
    # observation
    # ------------------------------------------------------------------
    def metrics(self, r: int) -> MetricsClient:
        return MetricsClient(self.metrics_base + r)

    def wait_for_replicas_up(self, timeout: float = 30.0,
                             replicas: Optional[List[int]] = None) -> None:
        pending = set(replicas if replicas is not None
                      else range(self.n)) - self.paused
        deadline = time.monotonic() + timeout
        while pending and time.monotonic() < deadline:
            for r in list(pending):
                if self.metrics(r).snapshot(timeout=0.3) is not None:
                    pending.discard(r)
            if pending:
                time.sleep(0.2)
        if pending:
            raise TimeoutError(f"replicas never came up: {sorted(pending)}")

    def wait_for(self, predicate, timeout: float = 30.0,
                 poll: float = 0.2):
        """Apollo-style polling assertion helper."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            v = predicate()
            if v:
                return v
            time.sleep(poll)
        raise TimeoutError("condition never satisfied")

    def last_executed(self, r: int) -> Optional[int]:
        return self.metrics(r).get("replica", "gauges", "last_executed_seq")

    def current_view(self, r: int) -> Optional[int]:
        return self.metrics(r).get("replica", "gauges", "view")

    # ------------------------------------------------------------------
    # clients
    # ------------------------------------------------------------------
    def _node_cfg(self) -> ReplicaConfig:
        return ReplicaConfig(f_val=self.f, c_val=self.c,
                             num_ro_replicas=self.num_ro,
                             num_of_client_proxies=self.num_clients,
                             threshold_scheme=self.threshold_scheme,
                             client_sig_scheme=self.client_sig_scheme)

    def _make_comm(self, node_id: int, eps):
        if self.transport in ("tls", "tls-mux"):
            from tpubft.comm import create_communication
            from tpubft.comm.multiplex import client_floor
            from tpubft.comm.tls import TlsConfig
            floor = (client_floor(self.n, self.num_ro)
                     if self.transport == "tls-mux" else None)
            return create_communication(
                TlsConfig(self_id=node_id, endpoints=eps,
                          certs_dir=self.certs_dir,
                          mux_client_floor=floor), self.transport)
        return PlainUdpCommunication(CommConfig(self_id=node_id,
                                                endpoints=eps))

    def client(self, idx: int = 0, **cfg_kw) -> BftClient:
        client_id = self.n + self.num_ro + idx
        cl = self._clients.get(client_id)
        if cl is None:
            cfg = self._node_cfg()
            keys = ClusterKeys.generate(
                cfg, self.num_clients,
                seed=self.seed.encode()).for_node(client_id)
            eps = endpoint_table(self.base_port, self.n + self.num_ro,
                                 self.num_clients)
            comm = self._make_comm(client_id, eps)
            cl = BftClient(ClientConfig(client_id=client_id, f_val=self.f,
                                        c_val=self.c, **cfg_kw), keys, comm)
            cl.start()
            self._clients[client_id] = cl
        return cl

    def skvbc_client(self, idx: int = 0, **cfg_kw) -> SkvbcClient:
        return SkvbcClient(self.client(idx, **cfg_kw))

    def operator_client(self, **cfg_kw):
        """Operator principal over the real transport (reconfiguration
        commands: wedge, key rotation, pruning — reference TesterCRE/
        concord-ctl roles)."""
        from tpubft.consensus.replicas_info import ReplicasInfo
        from tpubft.reconfiguration import OperatorClient
        cfg = self._node_cfg()
        op_id = ReplicasInfo.from_config(cfg).operator_id
        cl = self._clients.get(op_id)
        if cl is None:
            keys = ClusterKeys.generate(
                cfg, self.num_clients,
                seed=self.seed.encode()).for_node(op_id)
            eps = endpoint_table(self.base_port, self.n + self.num_ro,
                                 self.num_clients, operator_id=op_id)
            comm = self._make_comm(op_id, eps)
            cl = BftClient(ClientConfig(client_id=op_id, f_val=self.f,
                                        c_val=self.c, **cfg_kw), keys, comm)
            cl.start()
            self._clients[op_id] = cl
        return OperatorClient(cl)

    def __enter__(self) -> "BftTestNetwork":
        return self.start_all()

    def __exit__(self, *exc) -> None:
        self.stop_all()
