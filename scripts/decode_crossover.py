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

Three more cases run when named.  ``k2`` and ``g35``: the decode read
over latent rows ``[L, B, S, 640]`` (``ops.latent_attention``) at
``kimi-k2-ep32-stage.agent-saturated``'s grid and at
``gigachat35-ep16-stage.reason-saturated``'s one latent layer: checked
against the reference on the chip first (exits 1 on a gap), then the jnp
route (k2), the kernel of the commit before the in-kernel walk (from
``_parent/``: ``git archive <parent> | tar -x -C _parent``; left out where
that directory is missing) and the walk by page rows, pages a step and
buffers.  ``k2-experts``: a decode step's grouped products over the
experts it touches (``ops.grouped_matmul``: ``lax.ragged_dot`` on a
layer's slice against the kernel over the stacked matrices).  The
kernel-against-jnp claims of docs/KERNELS.md for both models are this
run's.
"""

import importlib.util
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


#: The latent grids: name -> (layers, slots, rows, heads, row width,
#: value width): kimi-k2-ep32-stage's 7 layers and
#: gigachat35-ep16-stage's one latent layer.
LATENT = {"k2": (7, 64, 4608, 64, 640, 512),
          "g35": (1, 96, 6144, 64, 640, 512)}
#: (page rows, pages a step, buffers) of the walks timed: one page a step
#: in two buffers is the walk alone, then steps of three pages, then a
#: third buffer, then the kernel's own rule (the last).
LATENT_WALKS = ((512, 1, 2), (512, 3, 2), (512, 3, 3), (256, 6, 3))
#: The experts a decode step meets: (expert layers, held, assignments'
#: rows, hidden, expert width).
EXPERTS = (6, 12, 512, 7168, 2048)


def parent_latent():
    """The latent kernel of the commit before the in-kernel walk, from
    ``_parent/`` (``git archive <parent> | tar -x -C _parent``), or None
    where that directory is missing."""
    path = os.path.join(REPO, "_parent", "cloud_tpu", "ops",
                        "latent_attention.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("parent_latent", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def latent_lengths(grid):
    """Lengths like the cell's (k2: half the rows in use; g35: about 30%),
    every row full, and (k2) no slot decoding: what a grid step costs
    with nothing to fetch."""
    _, slots, rows = LATENT[grid][:3]
    full = np.full(slots, rows, np.int32)
    if grid == "k2":
        rng = np.random.default_rng(35)
        half = rng.integers(1100, 3500, slots).astype(np.int32)
        return {"half-in-use": half, "full": full,
                "none-live": np.zeros(slots, np.int32)}
    rng = np.random.default_rng(39)
    return {"30%-in-use": rng.integers(600, 3100, slots).astype(np.int32),
            "full": full}


def check_latent(la, q, cache, value):
    """The walk against ``_reference`` on the chip: dead slots first, last
    and between live ones, lengths at a page's edges, and rows past every
    length (a stale page past the last live one) set to 1e4.  Exits 1 on a
    gap over bfloat16's rounding."""
    layers, slots, rows = cache.shape[:3]
    rng = np.random.default_rng(40)
    lens = rng.integers(1, rows + 1, slots).astype(np.int32)
    lens[[0, 3, 4, slots - 1]] = 0
    lens[[5, 6, 7, 8, 9]] = [1, 511, 512, 513, rows]
    lens = jnp.asarray(lens)
    past = jnp.arange(rows)[None, :, None] >= lens[:, None, None]
    noisy = cache.at[layers - 1].set(
        jnp.where(past, jnp.bfloat16(1e4), cache[layers - 1]))
    want = la._reference(q, cache[layers - 1], lens, value_dim=value,
                         scale=0.1)
    for page, per_step, buffers in LATENT_WALKS:
        got = jax.jit(lambda q, c, n: la._pallas(
            q, c, n, layers - 1, page, value_dim=value, scale=0.1,
            interpret=False, per_step=per_step, buffers=buffers))(
                q, noisy, lens)
        gap = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        print(json.dumps({"check": "latent",
                          "walk": [page, per_step, buffers],
                          "max_abs_gap": gap}), flush=True)
        if not gap <= 0.05:
            raise SystemExit(1)


def latent_case(grid):
    """The jnp route over layer ``l`` (k2 only), the parent's kernel and
    the walk over the stacked leaf by each of :data:`LATENT_WALKS`, at each
    of :func:`latent_lengths`."""
    la = sys.modules.get("cloud_tpu.ops.latent_attention") or \
        __import__("cloud_tpu.ops.latent_attention", fromlist=["x"])
    layers, slots, rows, heads, width, value = LATENT[grid]
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    cache = jax.random.normal(keys[0], (layers, slots, rows, width),
                              jnp.bfloat16)
    q = jax.random.normal(keys[1], (slots, heads, width), jnp.bfloat16)
    check_latent(la, q, cache, value)
    row_gb = width * 2 * layers / 1e9
    parent = parent_latent()

    def jnp_read(q, cache, lens, layer):
        out = la._reference(
            q, jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False),
            lens, value_dim=value, scale=0.1)
        return jnp.pad(out, ((0, 0), (0, 0), (0, width - value)))

    def kernel_read(module, page, **walk):
        def read(q, cache, lens, layer):
            out = module._pallas(q, cache, lens, layer, page,
                                 value_dim=value, scale=0.1,
                                 interpret=False, **walk)
            return jnp.pad(out, ((0, 0), (0, 0), (0, width - value)))
        return read

    def fetched(lens, unit):
        return sum(min(-(-int(n) // unit) * unit, rows) for n in lens)

    for name, lens in latent_lengths(grid).items():
        paths = [("jnp", None, jnp_read, slots * rows)] \
            if grid == "k2" else []
        if parent is not None:
            paths.append(("parent", [512], kernel_read(parent, 512),
                          fetched(lens, 512)))
        for page, per_step, buffers in LATENT_WALKS:
            paths.append(("walk", [page, per_step, buffers],
                          kernel_read(la, page, per_step=per_step,
                                      buffers=buffers),
                          fetched(lens, page)))
        for path, walk, read, rows_read in paths:
            ms = timed(read, layers, q, cache, jnp.asarray(lens))
            print(json.dumps({
                "grid": grid, "lengths": name, "path": path, "walk": walk,
                "ms_per_step": round(ms, 4),
                "gb_fetched": round(rows_read * row_gb, 4),
                "gb_per_s": round(rows_read * row_gb / ms * 1e3, 1),
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
    for grid in (g for g in grids if g in LATENT):
        latent_case(grid)
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
