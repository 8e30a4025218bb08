"""jit'd public wrappers for the Pallas kernels + kernel-backend selection.

The platform decides how a kernel runs: compiled on a TPU, through the
Pallas interpreter on a CPU host (semantics validation in tests).  Any
other platform is an error, and so is an explicit ``interpret=`` that
contradicts the platform — a TPU never silently runs the interpreter.

``resolve_backend`` maps the engine-facing choice (``"reference" |
"pallas" | "auto"``) to a concrete ``(backend, interpret)`` pair:
``"reference"`` is the pure-JAX path anywhere; ``"pallas"`` and ``"auto"``
are the Pallas kernels, interpreted on CPU and compiled on TPU.

Interfaces mirror the pure-JAX twins in repro.models.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.paged_attention import paged_attention_pallas

KERNEL_BACKENDS = ("reference", "pallas", "auto")
#: platform -> whether Pallas kernels run in the interpreter there
_INTERPRET_ON = {"cpu": True, "tpu": False}


def _interpret(interpret: Optional[bool] = None) -> bool:
    """The interpret flag the current platform requires; an explicit
    ``interpret`` that disagrees is an error, not an override."""
    platform = jax.default_backend()
    if platform not in _INTERPRET_ON:
        raise RuntimeError(
            f"Pallas kernels run compiled on TPU or interpreted on CPU; "
            f"platform {platform!r} has neither")
    want = _INTERPRET_ON[platform]
    if interpret is not None and interpret != want:
        raise ValueError(f"interpret={interpret} on platform {platform!r}: "
                         f"Pallas kernels run "
                         f"{'interpreted' if want else 'compiled'} there")
    return want


def resolve_backend(choice: str) -> Tuple[str, bool]:
    """Engine kernel choice -> (backend, interpret).

    "reference"        pure-JAX twins (layers.decode_attention & co).
    "pallas" / "auto"  Pallas kernels: compiled on TPU, interpreted on
                       CPU; any other platform raises.
    """
    if choice not in KERNEL_BACKENDS:
        raise ValueError(
            f"kernels={choice!r}: expected one of {KERNEL_BACKENDS}")
    if choice == "reference":
        return "reference", False
    return "pallas", _interpret()


@functools.partial(jax.jit,
                   static_argnames=("bq", "bkv", "causal", "interpret"))
def flash_attention(q, k, v, lengths=None, window=None, *, bq: int = 128,
                    bkv: int = 128, causal: bool = True,
                    interpret: Optional[bool] = None):
    """q: (B,S,H,dh); k/v: (B,S,KV,dh) -> (B,S,H,dh).

    ``lengths`` (B,) masks KV positions >= length per sequence; ``window``
    (scalar, python int or traced) masks q_pos - kv_pos >= window
    (sliding-window attention).  Both default to no-ops.
    """
    if window is not None and not causal:
        raise ValueError("flash_attention: window requires causal=True "
                         "(sliding windows are causal by definition)")
    return flash_attention_pallas(q, k, v, lengths=lengths, window=window,
                                  bq=bq, bkv=bkv, causal=causal,
                                  interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_attention(q, k_pages, v_pages, block_table, lengths, layer, *,
                    page_size: int, start=None, window=None,
                    interpret: Optional[bool] = None):
    """Decode: q (B,H,dh), one query per sequence at position length-1.
    Extend: q (B,S,H,dh) with ``start`` (B,), queries at start..start+S-1.
    k_pages/v_pages: (L,P,ps,KV*dh), the stacked pools of L layers (a
    single pool is L = 1); ``layer``: scalar index of the layer read;
    block_table: (B,maxp) int32; ``window`` as in flash_attention."""
    return paged_attention_pallas(q, k_pages, v_pages, block_table, lengths,
                                  layer, page_size=page_size, start=start,
                                  window=window,
                                  interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bc", "interpret"))
def moe_gmm(x, w, group_sizes, layer=None, *, bc: int = 128,
            interpret: Optional[bool] = None):
    """x: (E,C,d); w: (E,d,f), or the stacked (L,E,d,f) of L layers with
    ``layer`` the scalar index of the layer read; group_sizes: (E,)."""
    return moe_gmm_pallas(x, w, group_sizes, layer, bc=bc,
                          interpret=_interpret(interpret))
