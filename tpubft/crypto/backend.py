"""Crypto backend resolution: cpu | tpu | auto.

The reference selects its crypto engine statically (RELIC/Crypto++ at
build time); here the analogous choice is which side of the plugin
boundary executes — host OpenSSL-style verifiers or the batched device
kernels. The answer comes from the one place that knows: the platform
of `jax.devices()[0]` in THIS process. A chip serves one process, so
there is no child to ask and nothing to fall back to — a process that
cannot reach its device fails here, at construction, instead of
answering "cpu" and running slower forever.

  "cpu"  — host verifiers; JAX is never touched.
  "auto" — TPUBFT_CRYPTO_BACKEND ("cpu"/"tpu") if the operator set it,
           else "tpu" exactly when the default JAX platform is a TPU.
  "tpu"  — the device kernels. On a host whose default platform is not
           a TPU this is an error, unless JAX_PLATFORMS=cpu says the
           caller wants the XLA-CPU rehearsal of the device path (tests
           and CPU-mesh runs).
"""
from __future__ import annotations

import os


class BackendUnavailable(RuntimeError):
    """crypto_backend="tpu" was asked for and no TPU is reachable."""


def _rehearsal() -> bool:
    """JAX_PLATFORMS=cpu: the caller chose XLA-CPU on purpose."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _platform() -> str:
    import jax
    return jax.devices()[0].platform


def resolve_backend(requested: str) -> str:
    """Map a configured crypto_backend to a concrete one."""
    if requested == "auto":
        env = os.environ.get("TPUBFT_CRYPTO_BACKEND")
        if env not in ("cpu", "tpu"):
            return "tpu" if _platform() == "tpu" else "cpu"
        requested = env
    if requested == "tpu" and not _rehearsal():
        platform = _platform()
        if platform != "tpu":
            raise BackendUnavailable(
                f"crypto_backend='tpu' but the default JAX platform is "
                f"{platform!r}; set JAX_PLATFORMS=cpu to rehearse the "
                f"device path on XLA-CPU")
    return requested


def check_process_fanout(backend: str, n_processes: int) -> None:
    """Gate for launchers that start replica PROCESSES. A chip serves
    one process at a time, an unpinned JAX process takes every chip of
    its host, and nothing in this tree hands a child a chip of its own —
    so device-backed children beyond the first would fail or hang
    fighting for the device. Refuse instead of starting them. The
    XLA-CPU rehearsal (JAX_PLATFORMS=cpu) has no chip to fight over."""
    if backend == "cpu" or _rehearsal() or n_processes <= 1:
        return
    raise BackendUnavailable(
        f"refusing to start {n_processes} replica processes with "
        f"crypto_backend={backend!r}: a chip belongs to one process and "
        f"this launcher cannot give each child its own. Run the cluster "
        f"in one process (tpubft.testing.InProcessCluster shares the chip "
        f"through ops/dispatch.device_section), or see ROADMAP.md C6 "
        f"(one device-owning service per host).")
