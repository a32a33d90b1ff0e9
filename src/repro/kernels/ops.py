"""Jit'd dispatch wrappers for the Pallas kernels.

``impl`` selects the path:
  * "pallas"            real Mosaic lowering (TPU runtime)
  * "pallas_interpret"  kernel body executed on CPU (correctness tests)
  * "reference"         pure-jnp oracle the kernels are tested against
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import flash_decode as _flash_decode
from .flash_attention import flash_attention_fwd
from .rwkv6 import wkv6_chunked

DEFAULT_IMPL = "reference"


@partial(jax.jit, static_argnames=("window", "impl", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: Optional[int] = None,
                    impl: str = DEFAULT_IMPL,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: (BH, Sq, hd); k/v: (BHkv, Sk, hd)."""
    if impl == "reference":
        return ref.flash_attention_ref(q, k, v, window=window)
    return flash_attention_fwd(q, k, v, window=window, block_q=block_q,
                               block_k=block_k,
                               interpret=(impl == "pallas_interpret"))


@partial(jax.jit, static_argnames=("impl", "block_s"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, impl: str = DEFAULT_IMPL,
                     block_s: int = 256) -> jax.Array:
    """Flash decode. q: (BHkv, grp, hd); caches: (BHkv, S, hd);
    cache_len: (BHkv,)."""
    if impl == "reference":
        bhkv, grp, hd = q.shape
        qr = q.reshape(bhkv, 1, grp, hd).transpose(0, 1, 2, 3)
        # reference expects (B, 1, H, hd) + (B, S, Hkv, hd); here each
        # BHkv row is its own batch entry with one kv head
        from ..models.layers import decode_attention_ref
        out = decode_attention_ref(qr, k_cache[:, :, None, :],
                                   v_cache[:, :, None, :], cache_len)
        return out[:, 0]
    return _flash_decode(q, k_cache, v_cache, cache_len, block_s=block_s,
                         interpret=(impl == "pallas_interpret"))


@partial(jax.jit, static_argnames=("impl", "chunk"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, *, impl: str = DEFAULT_IMPL,
         chunk: int = 64) -> jax.Array:
    """r,k,v,w: (BH, S, hd); u: (BH, hd) — fp32 recurrence."""
    if impl == "reference":
        return ref.wkv6_ref(r, k, v, w, u)
    return wkv6_chunked(r.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), w.astype(jnp.float32),
                        u.astype(jnp.float32), chunk=chunk,
                        interpret=(impl == "pallas_interpret"))
