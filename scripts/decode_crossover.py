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

Two more cases, of the latent-attention expert model
(``kimi-k2-ep32-stage.agent-saturated``), run when named: ``k2``, the
decode read over latent rows ``[L, B, S, 640]`` (``ops.latent_attention``:
its jnp route against the kernel by page size), and ``k2-experts``, a
decode step's grouped products over the experts it touches
(``ops.grouped_matmul``: ``lax.ragged_dot`` on a layer's slice against the
kernel over the stacked matrices).  The kernel-against-jnp claims of
docs/KERNELS.md for both are this run's.
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


#: The latent grid: (layers, slots, rows, heads, row width, value width).
LATENT = (7, 64, 4608, 64, 640, 512)
LATENT_PAGES = (128, 256, 512)
#: The experts a decode step meets: (expert layers, held, assignments'
#: rows, hidden, expert width).
EXPERTS = (6, 12, 512, 7168, 2048)


def latent_case():
    """64 slots at lengths like the cell's (half the rows in use) and
    every row full: the jnp route over layer ``l`` against the kernel
    over the stacked leaf, by page."""
    la = sys.modules.get("cloud_tpu.ops.latent_attention") or \
        __import__("cloud_tpu.ops.latent_attention", fromlist=["x"])
    layers, slots, rows, heads, width, value = LATENT
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    cache = jax.random.normal(keys[0], (layers, slots, rows, width),
                              jnp.bfloat16)
    q = jax.random.normal(keys[1], (slots, heads, width), jnp.bfloat16)
    rng = np.random.default_rng(35)
    cases = {"half-in-use": rng.integers(1100, 3500, slots).astype(np.int32),
             "full": np.full(slots, rows, np.int32)}
    row_gb = width * 2 * layers / 1e9

    def jnp_read(q, cache, lens, layer):
        out = la._reference(
            q, jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False),
            lens, value_dim=value, scale=0.1)
        return jnp.pad(out, ((0, 0), (0, 0), (0, width - value)))

    def kernel_read(page):
        def read(q, cache, lens, layer):
            out = la._pallas(q, cache, lens, layer, page, value_dim=value,
                             scale=0.1, interpret=False)
            return jnp.pad(out, ((0, 0), (0, 0), (0, width - value)))
        return read

    for name, lens in cases.items():
        paths = [("jnp", None, jnp_read, slots * rows)]
        for page in LATENT_PAGES:
            paths.append(("kernel", page, kernel_read(page), sum(
                min(-(-int(n) // page) * page, rows) for n in lens)))
        for path, page, read, fetched in paths:
            ms = timed(read, layers, q, cache, jnp.asarray(lens))
            print(json.dumps({
                "grid": "k2", "lengths": name, "path": path, "page": page,
                "ms_per_step": round(ms, 4),
                "gb_fetched": round(fetched * row_gb, 4),
                "gb_per_s": round(fetched * row_gb / ms * 1e3, 1),
            }), flush=True)


def experts_case():
    """A decode step's three grouped products a layer, 16 of the 512
    assignments on 9 of the 12 held experts (and every expert touched):
    ``lax.ragged_dot`` on the layer's slice against the kernel over the
    stacked matrices."""
    gm = __import__("cloud_tpu.ops.grouped_matmul", fromlist=["x"])
    layers, held, rows, d, f = EXPERTS
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    stacks = [jax.random.normal(k, shape, jnp.bfloat16) * 0.01
              for k, shape in zip(keys, [(layers, held, d, f)] * 2
                                  + [(layers, held, f, d)])]
    x = jax.random.normal(keys[3], (rows, d), jnp.bfloat16)
    cases = {"9-touched": [3, 0, 1, 2, 0, 0, 4, 1, 2, 1, 1, 1],
             "12-touched": [2, 1, 1, 2, 1, 1, 2, 1, 2, 1, 1, 1]}

    def products(path):
        def read(x, stacks, sizes, layer):
            def one(x, w):
                if path == "kernel":
                    return gm.grouped_matmul(x, w, sizes, layer=layer,
                                             use_pallas=True)
                return gm._reference(x, w, sizes, layer)
            hidden = one(x, stacks[0]) * one(x, stacks[1])
            return one(hidden, stacks[2])
        return read

    for name, sizes in cases.items():
        touched = sum(1 for n in sizes if n)
        gb = touched * 3 * d * f * 2 * layers / 1e9
        for path in ("ragged_dot", "kernel"):
            ms = timed(products(path), layers, x, stacks,
                       jnp.asarray(sizes, jnp.int32))
            print(json.dumps({
                "grid": "k2-experts", "lengths": name, "path": path,
                "ms_per_step": round(ms, 4), "gb_touched": round(gb, 4),
                "gb_per_s": round(gb / ms * 1e3, 1)}), flush=True)


def main(grids):
    if jax.default_backend() != "tpu":
        raise SystemExit("decode_crossover.py times a TPU; none here")
    if "k2" in grids:
        latent_case()
    if "k2-experts" in grids:
        experts_case()
    for grid in (g for g in grids if g in GRIDS):
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
