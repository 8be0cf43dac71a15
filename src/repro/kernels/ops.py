"""Dispatch wrappers: Pallas kernel on TPU, interpret-mode kernel for CPU
validation, jnp oracle as the portable fallback.

mode: ``auto`` (compiled kernel on TPU, jnp oracle elsewhere) | ``pallas``
(compiled kernel; raises off the TPU) | ``interpret`` (the kernel through
the Pallas interpreter, for CPU validation) | ``ref`` (jnp oracle).  Only
an explicit ``interpret`` interprets: a ``pallas`` request on a host
without a TPU is an error, never a silent interpreter run.
"""
from __future__ import annotations

import jax

from .ref import sic_suffix_ref, ssd_scan_ref, swa_attention_ref
from .sic_suffix import sic_suffix_pallas
from .ssd_scan import ssd_scan_pallas
from .swa_attention import swa_attention_pallas

MODES = ("auto", "pallas", "interpret", "ref")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_ref(mode: str) -> bool:
    if mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; expected one of "
                         f"{MODES}")
    return mode == "ref" or (mode == "auto" and not _on_tpu())


def _interpret(mode: str) -> bool:
    """``interpret`` flag of a kernel call that ``_use_ref`` did not take."""
    if mode == "interpret":
        return True
    if not _on_tpu():
        raise RuntimeError(
            f"kernel mode 'pallas' needs a TPU backend, found "
            f"{jax.default_backend()!r}; use mode='interpret' to run the "
            "kernel through the Pallas interpreter")
    return False


def ssd_scan(x, dt, a, b, c, chunk: int = 128, mode: str = "auto"):
    if _use_ref(mode):
        return ssd_scan_ref(x, dt, a, b, c)
    return ssd_scan_pallas(x, dt, a, b, c, chunk=chunk,
                           interpret=_interpret(mode))


def sic_suffix_sum(w, block: int = 128, mode: str = "auto"):
    """Exclusive suffix sum along the last axis of ``w`` [..., N] — the SIC
    interference scan of the large-N power engine (``repro.core.sic``).
    ``ref`` is the jnp flip-cumsum oracle."""
    if _use_ref(mode):
        return sic_suffix_ref(w)
    flat = w.reshape((-1, w.shape[-1]))
    return sic_suffix_pallas(flat, block=block,
                             interpret=_interpret(mode)).reshape(w.shape)


def swa_attention(q, k, v, window: int = 0, softcap: float = 0.0,
                  block: int = 128, mode: str = "auto"):
    if _use_ref(mode):
        return swa_attention_ref(q, k, v, window=window, softcap=softcap)
    return swa_attention_pallas(q, k, v, window=window, softcap=softcap,
                                block=block, interpret=_interpret(mode))
