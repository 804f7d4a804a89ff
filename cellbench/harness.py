"""What every cell's run shares: the compile log and the ahead-of-time
warm-up (copied from chip_smoke.py, which ran on the chip in PR 21 — a
later PR may change the program, not the yardstick), the device's and
the breaker's evidence, and the loading of a cell's data files by the
names BENCHMARK.json gives."""
from __future__ import annotations

import importlib.util
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


_DEVICE = {}


def say(**row) -> None:
    """One JSON object per line on standard output, ahead of the
    result's line; each names the device the run is on."""
    print(json.dumps({**row, "device": _DEVICE}), flush=True)


# ---------------------------------------------------------------------
# the cell's data files, found by name
# ---------------------------------------------------------------------

def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


class Cell:
    """One entry of BENCHMARK.json's `workloads` with the files its
    names lead to: `configs/<config>.json`, `workloads/<name>.json`,
    `traffic/<traffic>.json`."""

    def __init__(self, name: str, manifest: dict | None = None) -> None:
        self.manifest = manifest or load_manifest()
        rows = [w for w in self.manifest["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.row = rows[0]
        self.name = name
        self.chips = self.row["chips"]
        self.config = load_json("configs", self.row["config"] + ".json")
        self.workload = load_json("workloads", name + ".json")
        self.traffic = load_json("traffic", self.row["traffic"] + ".json")

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        return [m for m in self.manifest["per_layer"] if self._reports(m)]


def load_by_name(folder: str, name: str):
    """The module `<folder>/<name>.py` — names may hold dots, so the
    file is found by path and not by import."""
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"cellbench.{folder}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------
# compile accounting and warm-up (chip_smoke.py's, PR 21)
# ---------------------------------------------------------------------

class CompileLog:
    """Every XLA compile this process makes, and whether JAX's
    persistent cache served it, from jax.monitoring events: rows of
    (jitted function, seconds in compile-or-load, cache hit). The hit
    event fires inside the timed span on the compiling thread, so the
    pairing is per thread."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self.rows = []
        self._tl = threading.local()
        self._mu = threading.Lock()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event, **kw) -> None:
        if event == self._HIT:
            self._tl.hit = True

    def _duration(self, event, secs, **kw) -> None:
        if event != self._COMPILE:
            return
        row = (kw.get("fun_name", ""), round(secs, 3),
               getattr(self._tl, "hit", False))
        self._tl.hit = False
        self._tl.last = row
        with self._mu:
            self.rows.append(row)

    def take_last(self):
        row, self._tl.last = getattr(self._tl, "last", None), None
        return row


def _s(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def ed25519_padded(n: int) -> int:
    """Lanes the single-device ed25519 tier pads an n-item batch to
    (ops/ed25519._single_device_verify's rule)."""
    from tpubft.ops import ed25519
    m = ed25519._pad_to_class(n)
    if ed25519._use_pallas():
        from tpubft.ops import ed25519_pallas
        tile = ed25519_pallas.TILE
        m = (max(m, tile) + tile - 1) // tile * tile
    return m


def single_device_programs(*, ed25519_batches=(), msm_points=(),
                           sha_uniform=()):
    """(label, jitted kernel, argument shapes) of the single-device
    programs a cell's traffic forms: `ed25519_batches` and `msm_points`
    are item counts, `sha_uniform` (messages, blocks) pairs. A cell's
    workload file names its own and no others."""
    import jax.numpy as jnp
    from tpubft.ops import bls12_381, ed25519, f25519, sha256
    from tpubft.ops.field import pad_pow2
    if ed25519._use_pallas():
        from tpubft.ops import ed25519_pallas
        ed_kernel = ed25519_pallas.verify_kernel
    else:
        ed_kernel = ed25519.verify_kernel
    i32 = jnp.int32
    out = []
    for m in sorted({ed25519_padded(n) for n in ed25519_batches}):
        out.append((f"ed25519@{m}", ed_kernel, [
            _s((64, m), i32), _s((64, m), i32), _s((f25519.NL, m), i32),
            _s((m,), i32), _s((f25519.NL, m), i32), _s((m,), i32)]))
    nl = bls12_381.g1_curve().f.nl if msm_points else 0
    for m in sorted({pad_pow2(n) for n in msm_points}):
        out.append((f"bls_msm@{m}", bls12_381.msm_kernel, [
            _s((bls12_381.SCALAR_BITS, m), i32), _s((nl, m), i32),
            _s((nl, m), i32), _s((m,), jnp.bool_)]))
    for n, nb in sha_uniform:
        out.append((f"sha256@{pad_pow2(n)}x{nb}", sha256.sha256_kernel,
                    [_s((pad_pow2(n), nb, 16), jnp.uint32)]))
    return out


def warm(programs, log: CompileLog) -> dict:
    """Compile every program before anything waits on it, ahead of time
    and into the persistent cache. Tracing holds the interpreter lock,
    so the programs are lowered one after another; each backend compile
    starts on a worker thread as soon as its program is lowered. Prints
    one row per program (seconds, and whether the cache already had
    it); returns {label: compiled}."""
    def compile_(label, lowered, trace_s):
        log.take_last()
        t0 = time.monotonic()
        compiled = lowered.compile()
        mine = log.take_last()
        row = dict(kernel=label, trace_s=trace_s,
                   compile_s=round(time.monotonic() - t0, 2),
                   cache=("in-process" if mine is None
                          else "hit" if mine[2] else "cold"))
        return row, compiled

    t0 = time.monotonic()
    with ThreadPoolExecutor(max(1, len(programs))) as pool:
        jobs = []
        for label, kernel, shapes in programs:
            t1 = time.monotonic()
            lowered = kernel.lower(*shapes)
            jobs.append(pool.submit(compile_, label, lowered,
                                    round(time.monotonic() - t1, 2)))
        done = [job.result() for job in jobs]
    for row, _ in done:
        say(phase="warm", **row)
    say(phase="warm", programs=len(done),
        wall_s=round(time.monotonic() - t0, 2),
        cold=sum(row["cache"] == "cold" for row, _ in done),
        cache_hits=sum(row["cache"] == "hit" for row, _ in done))
    return {row["kernel"]: compiled for row, compiled in done}


# ---------------------------------------------------------------------
# device evidence
# ---------------------------------------------------------------------

def kernel_profile() -> dict:
    """{kind: (calls, items)} the device seam has counted so far
    (`flight.kernel_profiler()`: calls and batch sizes are sound, its
    times are host clocks and are not read)."""
    from tpubft.utils import flight
    return {kind: (st["calls"], round(st["batch_avg"] * st["calls"]))
            for kind, st in flight.kernel_profiler().snapshot().items()}


def breaker_snapshot() -> dict:
    from tpubft.ops.dispatch import device_breaker
    return device_breaker().snapshot()


def breaker_events(since: dict) -> int:
    """Failures, trips and fast-fails the device breaker booked since
    `since`, plus 1 if it is not closed now. Every host tier behind a
    kernel is on the breaker's books (ops/dispatch.device_tier), so 0
    means the device answered every call it was given."""
    snap = breaker_snapshot()
    moved = sum(snap[c] - since[c]
                for c in ("failures", "trips", "fast_fails"))
    return moved + (snap["state"] != "closed")


def device_info() -> dict:
    import jax
    devs = jax.devices()
    _DEVICE.update(platform=devs[0].platform, kind=devs[0].device_kind,
                   count=len(devs))
    return dict(_DEVICE)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports
    it (0 where it reports none, as XLA-CPU does)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's own
    record of it — so that set-up counts the interpreter's start and
    the imports too."""
    try:
        with open("/proc/self/stat", encoding="ascii") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _IMPORTED_AT


_IMPORTED_AT = time.monotonic()


class Comparisons:
    """The numbers `correct` is decided by, each beside its limit; a
    number above its limit makes the run not correct."""

    def __init__(self) -> None:
        self.rows = {}

    def add(self, name: str, value, limit) -> None:
        self.rows[name] = {"value": value, "limit": limit}

    @property
    def correct(self) -> bool:
        return all(r["value"] <= r["limit"] for r in self.rows.values())
