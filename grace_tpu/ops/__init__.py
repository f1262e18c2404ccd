import os
import warnings

from grace_tpu.ops.packing import (pack_2bit, pack_3bit, pack_4bit,
                                   pack_bits, unpack_2bit, unpack_3bit,
                                   unpack_4bit, unpack_bits)
from grace_tpu.ops.sparse import scatter_dense

__all__ = ["pack_bits", "unpack_bits", "pack_2bit", "unpack_2bit",
           "pack_3bit", "unpack_3bit", "pack_4bit", "unpack_4bit",
           "scatter_dense", "pallas_disabled", "pallas_mode"]


def _env_true(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "no", "off")


def pallas_disabled(explicit: bool = False, kernel: str = "") -> bool:
    """Operational escape hatch: GRACE_DISABLE_PALLAS forces every Pallas
    kernel off. Nothing in the repository sets it (chip_smoke.py refuses
    to start under it; ROADMAP D4 decides its fate).
    ``kernel`` scopes the check: GRACE_DISABLE_PALLAS_<KERNEL> (e.g.
    ``_QUANT``, ``_TOPK``) disables only that kernel family. Warns when it defeats an explicit
    ``use_pallas=True`` — a forgotten export would otherwise turn the
    kernel equivalence tests into vacuous staged-vs-staged comparisons.
    Conventional false spellings ('', '0', 'false', 'no', 'off') mean NOT
    disabled."""
    var = None
    if _env_true("GRACE_DISABLE_PALLAS"):
        var = "GRACE_DISABLE_PALLAS"
    elif kernel and _env_true("GRACE_DISABLE_PALLAS_" + kernel.upper()):
        var = "GRACE_DISABLE_PALLAS_" + kernel.upper()
    if var is None:
        return False
    if explicit:
        warnings.warn(f"{var} is set: overriding explicit "
                      "use_pallas=True; Pallas kernels will NOT run",
                      RuntimeWarning, stacklevel=3)
    return True


def pallas_mode(use_pallas, kernel: str = "quant"):
    """The ONE fused-kernel selection rule: ``(enabled, interpret)`` for a
    ``use_pallas`` knob (True / False / 'auto') and a kernel family.
    ``True`` on a TPU is the compiled kernel; ``True`` elsewhere is
    interpret mode (a test vehicle); ``'auto'`` is the kernel on a TPU and
    the staged XLA path elsewhere. Which one ran is read from the compiled
    program (``tpu_custom_call``), as chip_smoke.py does.

    Every fused-kernel call site — the encode kernels
    (:mod:`grace_tpu.ops.pallas_quant`, family ``"quant"``) AND the
    decode/accumulate wire-path kernels
    (:mod:`grace_tpu.ops.pallas_wire`, family ``"wire"``) — resolves its
    path through this helper, so ``GRACE_DISABLE_PALLAS``, the per-family
    ``GRACE_DISABLE_PALLAS_<KERNEL>`` overrides, ``use_pallas='auto'``
    (kernel on real TPU, staged elsewhere) and the off-TPU interpret-mode
    fallback behave identically everywhere. Before this helper existed the
    codecs each carried a private copy of the rule and
    ``GRACE_DISABLE_PALLAS_QUANT`` only gated the encode side — a wire
    kernel added with its own copy would have been an env-var blind spot.
    """
    import jax

    if pallas_disabled(explicit=use_pallas is True, kernel=kernel):
        return False, False
    if use_pallas == "auto":
        return jax.default_backend() == "tpu", False
    if use_pallas is True:
        return True, jax.default_backend() != "tpu"
    return False, False
