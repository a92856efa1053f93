#!/usr/bin/env python
"""Flash-attention kernel microbench (run on the real TPU).

Compares the Pallas blockwise kernel against the materializing jnp
reference at growing sequence lengths; prints one JSON line per config.

Since flash became the standard-path default (``use_flash=None`` auto-
enables at seq >= 1024), each row also records the promotion contract:
``auto_default`` confirms the default routing picks the kernel at that
sequence length, and ``meets_floor`` asserts the measured speedup holds
the 1.29x the promotion was justified by (measured before PR 1) —
a row with ``meets_floor: false`` is a regression of the default path,
not just a slower kernel.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.attention import _auto_flash, FLASH_AUTO_SEQ_LEN
from deeplearning4j_tpu.ops.pallas import flash_attention
from deeplearning4j_tpu.parallel.unified import reference_attention


STEPS = 20
SPEEDUP_FLOOR = 1.29   # the measured win the default promotion rests on


def _chained(attn_fn):
    """20 data-dependent attention calls inside ONE jit — a single
    host↔device round trip, so dispatch latency can't pollute the
    per-call time."""
    @jax.jit
    def run(q, k, v):
        def body(_, acc):
            out = attn_fn(acc, k, v)
            return acc + 1e-6 * out          # data dependency between steps
        return jax.lax.fori_loop(0, STEPS, body, q)
    return run


def bench(fn, args):
    float(jnp.sum(fn(*args).astype(jnp.float32)))        # warm + compile
    t0 = time.perf_counter()
    float(jnp.sum(fn(*args).astype(jnp.float32)))        # hard sync
    return (time.perf_counter() - t0) / STEPS * 1000


def main():
    from deeplearning4j_tpu.config import place_compile_cache
    place_compile_cache()
    rng = np.random.default_rng(0)
    h, d = 8, 64
    for t in (4096, 8192, 16384, 32768):
        q = jnp.asarray(rng.normal(size=(2, t, h * d)).astype(np.float32)
                        ).astype(jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(2, t, h * d)).astype(np.float32)
                        ).astype(jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(2, t, h * d)).astype(np.float32)
                        ).astype(jnp.bfloat16)
        f = _chained(lambda a, b, c: flash_attention(
            a, b, c, n_heads=h, causal=True))   # flash_block=0 default path
        flash_ms = bench(f, (q, k, v))
        try:
            r = _chained(lambda a, b, c: reference_attention(
                a, b, c, n_heads=h, causal=True))
            ref_ms = bench(r, (q, k, v))
        except Exception:        # [T,T] materialization OOMs at long seq
            ref_ms = None
        speedup = None if ref_ms is None else round(ref_ms / flash_ms, 2)
        print(json.dumps({
            "metric": "flash_attention_ms", "seq_len": t, "value": round(flash_ms, 2),
            "platform": jax.devices()[0].platform,
            "unit": "ms", "reference_ms": None if ref_ms is None else round(ref_ms, 2),
            "speedup": speedup,
            # the promoted-default contract: this seq routes to flash by
            # default, and the speedup that justified the promotion holds
            "auto_default": bool(_auto_flash(q, k)) and t >= FLASH_AUTO_SEQ_LEN,
            "speedup_floor": SPEEDUP_FLOOR,
            "meets_floor": None if speedup is None else speedup >= SPEEDUP_FLOOR}))


if __name__ == "__main__":
    main()
