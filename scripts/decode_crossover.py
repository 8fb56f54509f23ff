"""Time the decode step's attention read on this chip: the XLA full-row
read of layer ``l`` of a carried ``[L, B, S, Hkv, hd]`` cache against the
paged kernel reading the same leaves in place, by page size, at the
benchmark's slot grids and at lengths like theirs.  Prints one JSON line
per (grid, lengths, path) — milliseconds per decode step (all layers),
the GB the path fetches and the rate on them.

This is the run ``ops.paged_attention.DEFAULT_PAGE_TOKENS`` and the
one-token shape's lack of a length gate were set from (docs/KERNELS.md has
its numbers).  Run it through the chip tool; it refuses to run off a TPU.

    python scripts/decode_crossover.py [grid ...]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

import cloud_tpu.ops  # noqa: F401  (binds the kernel modules)
from cloud_tpu.models.generation import _cache_attention

pa = sys.modules["cloud_tpu.ops.paged_attention"]

STEPS = 32  # decode steps a timed call
PAGES = (64, 128, 256)
#: name -> (layers, slots, rows, query heads, K/V heads, head_dim)
GRIDS = {
    "chat": (16, 12, 640, 32, 32, 128),   # baichuan-7b-l16.chat-open
    "docs": (16, 4, 2080, 32, 32, 128),   # baichuan-7b-l16.docs-saturated
    "h1": (6, 32, 640, 20, 4, 128),       # falcon-h1-34b-stage.chat-open
}


def lengths(grid):
    """Row lengths like the cell's (its ``kv_rows_in_use_pct`` and live
    slots, PERF.md section 5), every slot live at those lengths, and
    every row full: what the XLA read always pays for."""
    _, slots, rows = GRIDS[grid][:3]
    rng = np.random.default_rng(29)
    full = np.full(slots, rows, np.int32)
    if grid == "chat":
        ragged = np.zeros(slots, np.int32)
        ragged[[1, 4, 7, 10]] = [130, 225, 290, 410]
        return {"4-of-12-live": ragged, "full": full,
                "12-live": rng.integers(120, 330, slots).astype(np.int32)}
    if grid == "docs":
        return {"63%-in-use": np.array([1000, 1350, 1500, 1400], np.int32),
                "full": full}
    ragged = rng.integers(60, 520, slots).astype(np.int32)
    ragged[rng.permutation(slots)[:12]] = 0
    return {"20-of-32-live": ragged, "full": full}


def xla_read(q, cache, lens, layer):
    cache_l = {name: jax.lax.dynamic_index_in_dim(x, layer, keepdims=False)
               for name, x in cache.items()}
    return _cache_attention(q, cache_l, jnp.maximum(lens, 1))


def kernel_read(page):
    def read(q, cache, lens, layer):
        return pa._paged_pallas(q, cache, lens, None, None, page,
                                layer=layer, interpret=False)
    return read


def timed(read, layers, q, cache, lens):
    """ms per decode step: ``STEPS`` steps of ``layers`` reads chained
    through the queries, so nothing overlaps and nothing is elided."""
    @jax.jit
    def steps(q, cache, lens):
        def step(q, _):
            def layer(q, l):
                out = read(q, cache, lens, l)
                return (q + out * jnp.bfloat16(0.01)).astype(q.dtype), None
            return jax.lax.scan(layer, q, jnp.arange(layers))[0], None
        return jax.lax.scan(step, q, None, length=STEPS)[0]

    steps(q, cache, lens).block_until_ready()
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        steps(q, cache, lens).block_until_ready()
        best = min(best, time.perf_counter() - start)
    return best / STEPS * 1e3


def main(grids):
    if jax.default_backend() != "tpu":
        raise SystemExit("decode_crossover.py times a TPU; none here")
    for grid in grids:
        layers, slots, rows, heads, kv_heads, hd = GRIDS[grid]
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        shape = (layers, slots, rows, kv_heads, hd)
        cache = {"k": jax.random.normal(keys[0], shape, jnp.bfloat16),
                 "v": jax.random.normal(keys[1], shape, jnp.bfloat16)}
        q = jax.random.normal(keys[2], (slots, 1, heads, hd), jnp.bfloat16)
        row_gb = kv_heads * hd * 2 * 2 * layers / 1e9  # K and V, bf16
        for name, lens in lengths(grid).items():
            paths = [("xla", None, xla_read, slots * rows)]
            for page in PAGES:
                fetched = sum(min(-(-int(n) // page) * page, rows)
                              for n in lens if n > 0)
                paths.append(("kernel", page, kernel_read(page), fetched))
            for path, page, read, fetched in paths:
                ms = timed(read, layers, q, cache, jnp.asarray(lens))
                print(json.dumps({
                    "grid": grid, "lengths": name, "path": path,
                    "page": page, "ms_per_step": round(ms, 4),
                    "gb_fetched": round(fetched * row_gb, 4),
                    "gb_per_s": round(fetched * row_gb / ms * 1e3, 1),
                }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or list(GRIDS))
