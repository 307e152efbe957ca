"""jit'd public wrappers for the sketching kernels.

Every Pallas kernel compiles for the TPU by default (``interpret=False``);
``kernel_mode`` is the ONE place that decides whether a caller gets the
compiled kernel, the interpreted kernel or the pure-jnp oracle, and the
wrappers below (like the serve scheduler) resolve it once and pass the
result down as a static.  ``use_pallas=None`` picks the compiled kernel
on TPU and the jnp oracle everywhere else (the fast path on CPU and the
reference everywhere); ``use_pallas=True`` off-TPU runs the kernel body
in interpret mode — bit-identical block semantics, used by the
validation tests.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref
from repro.kernels.count_sketch import count_sketch as _cs_pallas
from repro.kernels.paged_attention import paged_attention as _pa_pallas
from repro.kernels.sketch_update import sketch_update as _su_pallas
from repro.kernels.unsketch import unsketch as _un_pallas


def kernel_mode(use_pallas: Optional[bool] = None) -> Optional[str]:
    """How a Pallas kernel runs here: "compiled", "interpret", or None
    for the jnp path.  ``use_pallas=None`` asks for the kernel only on a
    TPU; True asks for it anywhere, and off-TPU the kernel can only run
    interpreted; False asks for the jnp path."""
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu if use_pallas is None else use_pallas):
        return None
    return "compiled" if on_tpu else "interpret"


def count_sketch_op(x: jax.Array, h: jax.Array, s: jax.Array, J: int,
                    use_pallas: bool | None = None) -> jax.Array:
    """x: (B, I) -> (B, J)."""
    mode = kernel_mode(use_pallas)
    if mode:
        return _cs_pallas(x, h, s, J, interpret=mode == "interpret")
    return ref.count_sketch_ref(x, h, s, J)


def unsketch_op(y: jax.Array, h: jax.Array, s: jax.Array,
                use_pallas: bool | None = None) -> jax.Array:
    """y: (B, J) -> (B, I)."""
    mode = kernel_mode(use_pallas)
    if mode:
        return _un_pallas(y, h, s, interpret=mode == "interpret")
    return ref.unsketch_ref(y, h, s)


def sketch_update_op(g: jax.Array, m_table: jax.Array, v_table: jax.Array,
                     coeffs_m: jax.Array, coeffs_v: jax.Array, *,
                     b1: float, b2: float,
                     use_pallas: bool | None = None):
    """Fused sketched-moment update-retrieve for one flat gradient leaf.
    Returns (new_m, new_v, m_hat, v_hat) — see kernels/sketch_update.py."""
    mode = kernel_mode(use_pallas)
    if mode:
        return _su_pallas(g, m_table, v_table, coeffs_m, coeffs_v,
                          b1=b1, b2=b2, interpret=mode == "interpret")
    return ref.sketch_update_ref(g, m_table, v_table, coeffs_m, coeffs_v,
                                 b1, b2)


def paged_attention_op(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       tables: jax.Array, start: jax.Array,
                       fold_base: jax.Array,
                       kernels: Optional[str] = None):
    """Flash-decode paged attention statistics (kernels/paged_attention.py).
    q: (B, Sq, K, R, hd); pools (NB, bs, K, hd); tables (B, nb) int32;
    start/fold_base (B,) int32.  ``kernels`` is a resolved
    ``kernel_mode``: "compiled" / "interpret" run the Pallas kernel,
    None the jnp oracle.  Returns f32 (m, l, acc): (B, K, R, Sq) x2 and
    (B, K, R, Sq, hd)."""
    if kernels:
        return _pa_pallas(q, k_pool, v_pool, tables, start, fold_base,
                          interpret=kernels == "interpret")
    return ref.paged_attention_ref(q, k_pool, v_pool, tables, start,
                                   fold_base)
