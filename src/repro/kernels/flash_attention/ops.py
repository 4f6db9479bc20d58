"""jit'd public wrapper for the flash-attention Pallas kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from .flash_attention import flash_attention_pallas
from .ref import attention_ref


@partial(
    jax.jit, static_argnames=("causal", "window", "scale", "impl")
)
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    impl: str = "pallas",  # "pallas" | "xla"
) -> jax.Array:
    """Multi-head GQA attention: q (B,H,S,D), k/v (B,Hkv,S,D) -> (B,H,S,D).

    impl: "chunked" (portable flash-style scan, default for training cells),
    "pallas" (compiled on TPU, interpreted elsewhere), "xla" (naive — materialises the
    (B,H,S,S) scores; oracle + tiny shapes only).
    """
    if impl == "xla":
        return attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    if impl == "chunked" or q.shape[2] != k.shape[2]:
        # Cross-attention (unequal q/kv lengths) also takes this path.
        from repro.models.chunked_attention import attention_chunked

        return attention_chunked(q, k, v, causal=causal, window=window, scale=scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale
    )
