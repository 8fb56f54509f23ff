"""The chip's compiler, asked without the chip (compile, never a run).

libtpu compiles for a described ``v5e:2x2`` topology with no TPU attached,
so every Pallas kernel on the two main paths (ResNet50 trainer, SMALL
serving engine) is lowered here at its real width with ``interpret=False``.
Interpret mode accepts block shapes Mosaic refuses; these cases are what
stands between such a kernel and a chip call.

Rules of this file (the reason it is ONE file, and why nothing below runs
at import): only one process may load libtpu, so the topology is described
inside a module-scoped fixture — never at import, in a ``skipif``, in a
``parametrize`` argument or in a child process — and every sharding, mesh
and shape built from it is built in a fixture or a test.
"""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import cloud_tpu.ops  # noqa: F401  (binds the kernel modules)

gn_mod = sys.modules["cloud_tpu.ops.group_norm"]
fa_mod = sys.modules["cloud_tpu.ops.flash_attention"]
pa_mod = sys.modules["cloud_tpu.ops.paged_attention"]

RESNET50_STAGE_SHAPES = [
    (256, 32, 32, 64),
    (256, 32, 32, 256),
    (256, 16, 16, 512),
    (256, 8, 8, 1024),
    (256, 4, 4, 2048),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # A described-topology executable can be written to the persistent
    # cache but not read back without a chip; keep these compiles out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_spec(sharding, tree, dtype=None):
    """A tree of shapes on ``sharding``, recast to ``dtype`` if given."""
    return jax.tree_util.tree_map(
        lambda x: _spec(x.shape, dtype or x.dtype, sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ---------------------------------------------------------------------------
# GroupNorm: the five ResNet50 stage shapes of the b256 CIFAR headline
# ---------------------------------------------------------------------------


def _gn_args(shape, sharding):
    x = _spec(shape, jnp.bfloat16, sharding)
    vec = _spec((shape[-1],), jnp.float32, sharding)
    return x, vec, vec


@pytest.mark.parametrize("shape", RESNET50_STAGE_SHAPES, ids=str)
def test_group_norm_fwd(one_chip, shape):
    fn = functools.partial(
        gn_mod.group_norm, num_groups=32, use_pallas=True,
        partitioned=False, activation="relu",
    )
    _compile(fn, *_gn_args(shape, one_chip))


@pytest.mark.parametrize("shape", RESNET50_STAGE_SHAPES, ids=str)
def test_group_norm_grad(one_chip, shape):
    def loss(x, scale, bias):
        y = gn_mod.group_norm(
            x, scale, bias, num_groups=32, use_pallas=True,
            partitioned=False, activation="relu",
        )
        return jnp.sum(y.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), *_gn_args(shape, one_chip))


@pytest.mark.parametrize("shape", RESNET50_STAGE_SHAPES, ids=str)
def test_group_norm_fused_residual_grad(one_chip, shape):
    def loss(x, scale, bias, residual):
        y = gn_mod.group_norm(
            x, scale, bias, num_groups=32, use_pallas=True,
            partitioned=False, activation="relu", residual=residual,
        )
        return jnp.sum(y.astype(jnp.float32))

    x, vec, _ = _gn_args(shape, one_chip)
    _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), x, vec, vec, x)


def _mesh(topo, sizes):
    """The four described chips as the framework's six-axis mesh, the way
    ``MeshSpec(sizes).build()`` lays out attached ones."""
    from cloud_tpu import parallel
    from cloud_tpu.parallel import mesh as mesh_lib

    return Mesh(
        np.array(topo.devices).reshape(parallel.MeshSpec(sizes).shape()),
        mesh_lib.CANONICAL_AXES,
    )


def _kernel_lines(compiled):
    return [ln for ln in compiled.as_text().splitlines()
            if "tpu_custom_call" in ln and "bf16[" in ln]


def test_group_norm_mesh_route_shards_the_batch(topo):
    """Under a dp=4 global mesh the kernel is in the per-device program
    and takes a quarter of the batch (sharded, not replicated)."""
    from cloud_tpu import parallel

    mesh = _mesh(topo, {"dp": 4})
    shape = (256, 16, 16, 512)
    x = _spec(shape, jnp.bfloat16, NamedSharding(mesh, P("dp")))
    vec = _spec((shape[-1],), jnp.float32, NamedSharding(mesh, P()))

    def loss(x, scale, bias):
        y = gn_mod.group_norm(
            x, scale, bias, num_groups=32, use_pallas=True,
            activation="relu", batch_axes="dp",
        )
        return jnp.sum(y.astype(jnp.float32))

    with parallel.use_mesh(mesh):
        compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, vec, vec)
    calls = _kernel_lines(compiled)
    assert calls and all("bf16[64,16,16,512]" in ln for ln in calls), calls
    assert not any("bf16[256,16,16,512]" in ln for ln in calls), calls


# ---------------------------------------------------------------------------
# Flash attention: causal LM shape and the masked (prefill / BERT) shape
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,causal,masked", [
    ((8, 1024, 12, 64), True, False),
    ((32, 512, 12, 64), False, True),
    ((8, 1024, 12, 64), True, True),   # causal + a generic key-side mask
    ((8, 1024, 12, 64), True, "lengths"),  # serving prefill: the lengths
], ids=["causal-1024", "masked-512", "causal-masked-1024",
        "causal-lengths-1024"])
def test_flash_fwd_and_grad(one_chip, shape, causal, masked):
    qkv = _spec(shape, jnp.bfloat16, one_chip)
    rows = {True: ("mask", _spec(shape[:2], jnp.bool_, one_chip)),
            "lengths": ("lengths", _spec(shape[:1], jnp.int32, one_chip)),
            False: None}[masked]

    def loss(q, k, v, *row):
        out = fa_mod.flash_attention(
            q, k, v, causal=causal, use_pallas=True,
            **({rows[0]: row[0]} if row else {})
        )
        return jnp.sum(out.astype(jnp.float32))

    args = (qkv, qkv, qkv) + ((rows[1],) if rows else ())
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), *args)


def test_flash_mesh_route_shards_the_heads(topo):
    """Serving prefill on a tp=4 slice: the masked causal kernel runs on a
    quarter of the heads per chip ([B, H/4, T, D] inside the kernel)."""
    mesh = _mesh(topo, {"tp": 4})
    qkv = _spec((1, 1024, 12, 64), jnp.bfloat16,
                NamedSharding(mesh, P(None, None, "tp", None)))
    mask = _spec((1, 1024), jnp.int32, NamedSharding(mesh, P()))

    def fn(q, k, v, mask):
        return fa_mod.flash_attention(
            q, k, v, causal=True, mask=mask, use_pallas=True,
            partitioned=True, mesh=mesh, head_axes="tp",
        )

    calls = _kernel_lines(_compile(fn, qkv, qkv, qkv, mask))
    assert calls and all("bf16[1,3,1024,64]" in ln for ln in calls), calls


# ---------------------------------------------------------------------------
# Paged attention: 16 slots x 1152 x 12 heads x 64, with a 64 x 16 pool
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("has_pool", [False, True], ids=["slot", "pool"])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8kv"])
@pytest.mark.parametrize("tq", [1, 8], ids=["decode", "chunk"])
def test_paged(one_chip, tq, kv_dtype, has_pool):
    slots, s, h, hd, blocks, bt = 16, 1152, 12, 64, 64, 16

    def leaves(lead):
        out = {"k": _spec((*lead, h, hd), kv_dtype, one_chip),
               "v": _spec((*lead, h, hd), kv_dtype, one_chip)}
        if kv_dtype == jnp.int8:
            out["k_scale"] = _spec((*lead, h, 1), jnp.float32, one_chip)
            out["v_scale"] = _spec((*lead, h, 1), jnp.float32, one_chip)
        return out

    q = _spec((slots, tq, h, hd), jnp.bfloat16, one_chip)
    cur_len = _spec((slots,), jnp.int32, one_chip)
    cache_l = leaves((slots, s))
    pool_l = leaves((blocks, bt)) if has_pool else None
    page = bt if has_pool else pa_mod._fit_page(s, None)
    table = _spec((slots, -(-s // page)), jnp.int32, one_chip)

    def fn(q, cache_l, cur_len, pool_l, table):
        return pa_mod._paged_pallas(
            q, cache_l, cur_len, pool_l, table, page, interpret=False
        )

    _compile(fn, q, cache_l, cur_len, pool_l, table)


def test_paged_mesh_route_shards_the_heads(topo, monkeypatch):
    """The decode read on a tp=4 slice: each chip's kernel reads its own
    three heads of the stacked slot rows, in place (the whole
    ``[L, B, S, H/4, hd]`` shard is the operand, the layer an index)."""
    # The public entry point turns the interpreter on by itself off-TPU;
    # this process is on the CPU and compiles for the chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _mesh(topo, {"tp": 4})
    layers, slots, s, h, hd = 4, 16, 1152, 12, 64
    whole = NamedSharding(mesh, P())
    q = _spec((slots, 1, h, hd), jnp.bfloat16,
              NamedSharding(mesh, P(None, None, "tp", None)))
    heads = NamedSharding(mesh, P(None, None, None, "tp", None))
    cache = {"k": _spec((layers, slots, s, h, hd), jnp.bfloat16, heads),
             "v": _spec((layers, slots, s, h, hd), jnp.bfloat16, heads)}
    cur_len = _spec((slots,), jnp.int32, whole)
    table = _spec((slots, s // 128), jnp.int32, whole)
    layer = _spec((), jnp.int32, whole)

    def fn(q, cache, cur_len, table, layer):
        return pa_mod.paged_decode_attention(
            q, cache, cur_len, layer=layer, block_table=table,
            use_pallas=True, partitioned=True, mesh=mesh, head_axes="tp",
        )

    calls = _kernel_lines(_compile(fn, q, cache, cur_len, table, layer))
    assert calls and all("bf16[4,16,1152,3,64]" in ln for ln in calls), calls
    assert not any("bf16[4,16,1152,12,64]" in ln for ln in calls), calls


# ---------------------------------------------------------------------------
# Slot-grid programs: the KV cache is updated in place, never moved whole
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("program", [
    "decode_chunk_program", "prefill_chunk_program", "verify_chunk_program",
])
def test_slot_cache_is_updated_in_place(one_chip, program, kv_quant):
    """The cache rides the layer loop as the carry: the compiled program
    has no ``copy``, ``dynamic-update-slice`` or ``AllocateBuffer`` whose
    result is a whole K or V cache, and its temp space is under half the
    cache's bytes.  (As scan ``xs``/``ys`` the cache was sliced, stacked
    into a fresh buffer and copied back — four whole-cache moves a decode
    step — and temp was the cache's size or more.)  4 layers, 4 slots x
    2048 rows, 16 heads x 128: 268 MB of bf16 cache, donated.

    An int8 cache's SCALE leaves ([..., H, 1] f32, 3% of its bytes) are
    re-laid-out once at the program's entry and exit, outside every loop:
    that is their shape's cost, not a cache move, and the temp bound
    holds them."""
    from cloud_tpu.models import generation, transformer

    slots, rows = 4, 2048
    config = transformer.TransformerConfig(
        vocab_size=32000, num_layers=4, dim=2048, num_heads=16,
        head_dim=128, mlp_hidden=5632, max_seq_len=rows, remat=False,
    )
    sample = generation.SampleConfig(temperature=0.0)

    on_chip = functools.partial(_tree_spec, one_chip)
    params = on_chip(jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), config)),
        jnp.bfloat16)
    cache = on_chip(jax.eval_shape(lambda: generation.init_slot_cache(
        config, slots, rows, kv_quant=kv_quant)))
    state = on_chip(jax.eval_shape(lambda: generation.init_slot_state(
        config, slots, sample=sample)))
    scalar = _spec((), jnp.int32, one_chip)
    if program == "decode_chunk_program":
        def fn(params, cache, state):
            return generation.decode_chunk_program(
                params, cache, state, config, chunk_size=8, sample=sample)
        args = (params, cache, state)
    elif program == "prefill_chunk_program":
        def fn(params, cache, tokens, start, chunk_len, slot):
            return generation.prefill_chunk_program(
                params, cache, tokens, start, chunk_len, slot, config)
        args = (params, cache, _spec((1, 64), jnp.int32, one_chip),
                scalar, scalar, scalar)
    else:
        def fn(params, cache, state, window):
            return generation.verify_chunk_program(
                params, cache, state, window, config, sample=sample)
        args = (params, cache, state, _spec((slots, 4), jnp.int32, one_chip))

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    _assert_cache_in_place(compiled, cache)


def test_insert_at_two_widths_is_one_program_that_copies_no_cache(
        one_chip, monkeypatch):
    """The insert program of a 2048 buffer: ONE executable that holds the
    layer stack at both of the buffer's widths (a ``conditional`` over
    two branches, the flash kernel once in each, at 1536 and at 2048
    rows), each branch writing the donated slot cache in place: no
    ``copy`` or ``AllocateBuffer`` of a whole K or V cache on either
    side of the switch, temp under half the cache's bytes.  A 1024
    buffer has one width and no ``conditional``.  The same 4-layer model
    as :func:`test_slot_cache_is_updated_in_place`."""
    from cloud_tpu.models import generation, transformer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, rows = 4, 2048 + 32
    config = transformer.TransformerConfig(
        vocab_size=32000, num_layers=4, dim=2048, num_heads=16,
        head_dim=128, mlp_hidden=5632, max_seq_len=rows, remat=False,
    )
    sample = generation.SampleConfig(temperature=0.0)
    on_chip = functools.partial(_tree_spec, one_chip)
    params = on_chip(jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), config)),
        jnp.bfloat16)
    cache = on_chip(jax.eval_shape(
        lambda: generation.init_slot_cache(config, slots, rows)))
    state = on_chip(jax.eval_shape(lambda: generation.init_slot_state(
        config, slots, sample=sample)))
    scalar = _spec((), jnp.int32, one_chip)

    def insert(params, cache, state, tokens, prompt_len, slot, new):
        return generation.insert_slot_program(
            params, cache, state, tokens, prompt_len, slot, new, config,
            sample=sample)

    def compiled_at(bucket):
        tokens = _spec((1, bucket), jnp.int32, one_chip)
        return jax.jit(insert, donate_argnums=(1, 2)).lower(
            params, cache, state, tokens, scalar, scalar, scalar).compile()

    two = compiled_at(2048)
    text = two.as_text()
    assert len(re.findall(r" conditional\(", text)) == 1
    # (The kernel's output has the sequence in the lanes: [B, H, Dv, T].)
    flash = re.findall(r"%flash_fwd[\w.]* = \(bf16\[1,16,128,(\d+)\]", text)
    assert sorted(map(int, flash)) == [1536, 2048], flash
    _assert_cache_in_place(two, cache, in_place=("dynamic-update-slice",))
    one = compiled_at(1024).as_text()
    assert " conditional(" not in one
    assert len(re.findall(r"%flash_fwd[\w.]* = ", one)) == 1


def _assert_cache_in_place(compiled, cache, in_place=()):
    kv = "{}[{}]".format("s8" if "k_scale" in cache else "bf16",
                         ",".join(map(str, cache["k"].shape)))
    moved = [
        line.strip()[:200] for line in compiled.as_text().splitlines()
        if re.match(r"\s*(ROOT )?%[\w.\-]+ = " + re.escape(kv) + r"\S* "
                    r"(copy|dynamic-update-slice|custom-call)\(", line)
        and ("custom-call(" not in line or "AllocateBuffer" in line)
        and not any(f" {op}(" in line for op in in_place)
    ]
    assert not moved, moved
    cache_bytes = sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in cache.values())
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 2, (temp, cache_bytes)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("kv_heads", [None, 4], ids=["mha", "grouped"])
def test_decode_chunk_reads_the_carried_cache_in_place(
        one_chip, monkeypatch, kv_heads, kv_quant):
    """On a TPU the decode read is the paged kernel, with nothing to
    switch it on: the compiled chunk program holds ONE ``paged_decode``
    call (the layer loop's) whose K/V operands are the whole stacked
    ``[L, B, S, Hkv, hd]`` leaves, nothing in it produces a
    ``[B, S, Hkv, hd]`` layer of K or V (no slice, no copy before the
    call), and the cache is still the loop's carry, updated in place.
    An int8 cache keeps the XLA read (its scale leaves would reach the
    kernel re-laid-out whole): no kernel in the program."""
    from cloud_tpu.models import generation, transformer

    # Auto-dispatch asks the default backend; this process is on the CPU
    # and compiles for the chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, rows = 4, 2048
    config = transformer.TransformerConfig(
        vocab_size=32000, num_layers=4, dim=2048, num_heads=16,
        num_kv_heads=kv_heads, head_dim=128, mlp_hidden=5632,
        max_seq_len=rows, remat=False,
    )
    sample = generation.SampleConfig(temperature=0.0)

    on_chip = functools.partial(_tree_spec, one_chip)
    params = on_chip(jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), config)),
        jnp.bfloat16)
    cache = on_chip(jax.eval_shape(lambda: generation.init_slot_cache(
        config, slots, rows, kv_quant=kv_quant)))
    state = on_chip(jax.eval_shape(lambda: generation.init_slot_state(
        config, slots, sample=sample)))

    def fn(params, cache, state):
        return generation.decode_chunk_program(
            params, cache, state, config, chunk_size=8, sample=sample)

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, state).compile()
    lines = compiled.as_text().splitlines()
    calls = [ln for ln in lines
             if re.match(r"\s*%paged_decode(\.\d+)? = ", ln)]
    _assert_cache_in_place(compiled, cache)
    if kv_quant:
        assert not calls and "tpu_custom_call" not in compiled.as_text()
        return
    assert len(calls) == 1, calls
    dtype = "s8" if kv_quant else "bf16"
    stacked = "{}[{}]".format(dtype, ",".join(map(str, cache["k"].shape)))
    constraints = calls[0].split("operand_layout_constraints={")[1]
    assert constraints.split("}}")[0].count(stacked) == 2, calls[0][:2000]
    layer = "{}[{}]".format(dtype, ",".join(map(str, cache["k"].shape[1:])))
    sliced = [ln.strip()[:200] for ln in lines
              if re.match(r"\s*(ROOT )?%[\w.\-]+ = " + re.escape(layer), ln)]
    assert not sliced, sliced


# ---------------------------------------------------------------------------
# The hybrid decode chunk at falcon-h1-34b-stage.chat-open's shapes: the
# recurrent state is advanced in place, by one kernel call a layer
# ---------------------------------------------------------------------------


def test_hybrid_decode_chunk_advances_the_state_leaf_in_place(
        one_chip, monkeypatch):
    """The cell's chunk program (32 slots x 640 rows, 6 blocks at the
    published widths) holds ONE ``ssm_state_step`` call (the layer
    loop's) whose operand and result is the whole carried
    ``f32[6,32,32,128,256]`` leaf, aliased; nothing else in the program
    produces that leaf (no copy, no dynamic-update-slice, no fresh
    buffer) or a layer of it, and nothing else reads it; temp is no
    larger than the parent's 0.80 GB (the four re-laid-out projection
    kernels: ``benchmarks/rehearse_compile_hybrid.py`` at PR 30)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    monkeypatch.syspath_prepend(root)
    from benchmarks.adapters import serve_hybrid
    from benchmarks.harness import manifest
    from benchmarks.references import falcon_h1
    from cloud_tpu.models import generation

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = manifest.Cell("falcon-h1-34b-stage.chat-open", root=root)
    sizes, engine = cell.config, cell.traffic["engine"]
    config = serve_hybrid.model_config(sizes, cell.traffic)
    sample = generation.SampleConfig(temperature=0.0)
    rows = engine["prompt_buckets"][-1] + engine["max_new_tokens"]

    on_chip = functools.partial(_tree_spec, one_chip)
    params = on_chip(falcon_h1.params_shape(sizes))
    cache = on_chip(jax.eval_shape(lambda: generation.init_slot_cache(
        config, engine["num_slots"], rows)))
    state = on_chip(jax.eval_shape(lambda: generation.init_slot_state(
        config, engine["num_slots"], sample=sample)))

    def fn(params, cache, state, rng):
        return generation.decode_chunk_program(
            params, cache, state, config, chunk_size=engine["chunk_tokens"],
            sample=sample, rng=rng)

    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, cache, state, _spec((2,), jnp.uint32, one_chip)).compile()
    assert cache["ssm"].shape == (6, 32, 32, 128, 256)
    leaf = "f32[6,32,32,128,256]"
    lines = compiled.as_text().splitlines()
    made = {}  # instruction name -> opcode, of everything typed as the leaf
    for line in lines:
        match = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\(?)" + re.escape(leaf)
                         + r"\S* .*?([\w\-]+)\(", line)
        if match:
            made[match.group(1)] = (match.group(3), bool(match.group(2)))
    calls = [name for name in made
             if re.match(r"%ssm_state_step(\.\d+)?$", name)]
    assert len(calls) == 1, made
    call_line = next(ln for ln in lines
                     if re.match(r"\s*" + re.escape(calls[0]) + " = ", ln))
    assert "output_to_operand_aliasing={{0}: (8, {})}" in call_line
    # The leaf only ever comes out of a parameter, a loop's tuple or the
    # call: never out of a copy, an update, a fusion or a fresh buffer.
    passed_on = {"parameter", "get-tuple-element", "while", "tuple"}
    moved = {name: op for name, (op, _) in made.items()
             if op not in passed_on and name not in calls}
    assert not moved, moved
    # ...and only the call (and the loops' plumbing) takes it as operand.
    holders = {name for name, (_, in_tuple) in made.items() if not in_tuple}
    readers = []
    for line in lines:
        match = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = .*? ([\w\-]+)\((.*)",
                         line)
        if not match or match.group(2) in passed_on or \
                match.group(1) in calls:
            continue
        if holders & set(re.findall(r"%[\w.\-]+", match.group(3))):
            readers.append(line.strip()[:200])
    assert not readers, readers
    assert not [ln for ln in lines if "f32[32,32,128,256]" in ln]
    assert compiled.memory_analysis().temp_size_in_bytes <= 0.81e9


# ---------------------------------------------------------------------------
# The latent-attention expert model (kimi-k2-ep32-stage.agent-saturated):
# its two kernels and the flash kernel at head size 192, at the published
# widths, and its chunk program with both carried in place
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("page", [128, 256, 512])
def test_latent_decode(one_chip, page):
    """64 slots x 4,608 rows of 640, layer 3 of the stack of 7, 64 heads."""
    from cloud_tpu.ops import latent_attention

    def fn(q, rows, lens, layer):
        return latent_attention._pallas(
            q, rows, lens, layer, page, value_dim=512, scale=0.1,
            interpret=False)

    _compile(fn, _spec((64, 64, 640), jnp.bfloat16, one_chip),
             _spec((7, 64, 4608, 640), jnp.bfloat16, one_chip),
             _spec((64,), jnp.int32, one_chip), _spec((), jnp.int32, one_chip))


def test_latent_decode_at_the_hybrid_models_grid(one_chip):
    """gigachat35-ep16-stage.reason-saturated's read: 96 slots x 6,144
    rows of 640, its one latent layer, 64 heads, at the dispatch's page."""
    from cloud_tpu.ops import latent_attention

    q = _spec((96, 64, 640), jnp.bfloat16, one_chip)
    rows = _spec((1, 96, 6144, 640), jnp.bfloat16, one_chip)
    page = latent_attention.kernel_page(q, rows, use_pallas=True)

    def fn(q, rows, lens, layer):
        return latent_attention._pallas(
            q, rows, lens, layer, page, value_dim=512, scale=0.1,
            interpret=False)

    text = _compile(fn, q, rows, _spec((96,), jnp.int32, one_chip),
                    _spec((), jnp.int32, one_chip)).as_text()
    # The rows go in whole (the walk fetches its pages itself).
    assert "bf16[96,6144,640]" not in text


@pytest.mark.parametrize("rows, k, n", [(512, 7168, 2048), (512, 2048, 7168),
                                        (1024, 7168, 2048),
                                        (1024, 2048, 7168)])
def test_grouped_matmul(one_chip, rows, k, n):
    """A decode step's 512 assignments and a prompt's block of 1,024
    against 12 held experts of a stack of 6 layers, gate/up and down."""
    from cloud_tpu.ops import grouped_matmul

    tm, tn = grouped_matmul._tiles(rows, k, n, 2)

    def fn(x, w, sizes, layer):
        return grouped_matmul._pallas(x, w, sizes, layer, tm, tn,
                                      interpret=False)

    compiled = _compile(
        fn, _spec((rows, k), jnp.bfloat16, one_chip),
        _spec((6, 12, k, n), jnp.bfloat16, one_chip),
        _spec((12,), jnp.int32, one_chip), _spec((), jnp.int32, one_chip))
    # The stack goes in whole: nothing the size of a layer's is made.
    assert f"bf16[12,{k},{n}]" not in compiled.as_text()


def test_flash_at_the_latent_models_head_size(one_chip):
    """The call as the cell makes it at its 4,096 width: query/key heads
    of 192 (128 + 64 rotated), values at their own 128, 64 heads, the
    row's length in place of a mask; value and gradient, so the backward
    kernels take the value head's size too."""
    from cloud_tpu import ops

    def loss(q, k, v, lengths):
        out = ops.flash_attention(q, k, v, causal=True, lengths=lengths,
                                  use_pallas=True)
        assert out.shape == (1, 4096, 64, 128)
        return jnp.sum(out.astype(jnp.float32))

    qk = _spec((1, 4096, 64, 192), jnp.bfloat16, one_chip)
    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), qk, qk,
        _spec((1, 4096, 64, 128), jnp.bfloat16, one_chip),
        _spec((1,), jnp.int32, one_chip))
    # The kernel's output has the sequence in the lanes: [B, H, Dv, T].
    assert re.search(r"%\w*flash_fwd[\w.]* = \(bf16\[1,64,128,4096\]",
                     compiled.as_text())


def _latent_prefill_text(one_chip, monkeypatch):
    """The compiled forward pass of the cell's insert at its 4,096 width
    (1 dense + 6 expert layers at the published widths)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    monkeypatch.syspath_prepend(root)
    from benchmarks.adapters import serve_latent_moe
    from benchmarks.harness import manifest
    from benchmarks.references import kimi_k2
    from cloud_tpu.models import generation
    from cloud_tpu.parallel.sharding import DEFAULT_RULES

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = manifest.Cell("kimi-k2-ep32-stage.agent-saturated", root=root)
    config = serve_latent_moe.model_config(cell.config, cell.traffic)
    params = _tree_spec(one_chip, kimi_k2.params_shape(cell.config))

    def fn(params, tokens, lens):
        return generation._prefill_forward(params, tokens, lens, config,
                                           DEFAULT_RULES, None)

    return jax.jit(fn).lower(
        params, _spec((1, 4096), jnp.int32, one_chip),
        _spec((1,), jnp.int32, one_chip)).compile().as_text()


def test_latent_prefill_pads_no_values(one_chip, monkeypatch):
    """The forward pass of the cell's insert at its 4,096 width: the flash
    kernel is in both stacks and its output has the values' own head size,
    128 (the kernel's output takes the size of the values it is handed:
    padded to the keys' 192 they came back at 192 and were sliced); of its
    operands only the queries and the keys have heads of 192.  (Queries,
    values and output have the sequence in the lanes: [B, H, D, T].)"""
    text = _latent_prefill_text(one_chip, monkeypatch)
    flash = re.findall(r"%flash_fwd[\w.]* = \((bf16\[[\d,]+\])", text)
    assert len(flash) == 2 and set(flash) == {"bf16[1,64,128,4096]"}, flash
    calls = [ln for ln in text.splitlines()
             if re.match(r"\s*%flash_fwd[\w.]* = ", ln)]
    for call in calls:
        operands = re.findall(r"%[\w.\-]+", call.split("custom-call(")[1]
                              .split(")")[0])
        shapes = [re.search(re.escape(name) + r" = (\w+\[[\d,]*\])", text)
                  .group(1) for name in operands]
        assert sorted(s for s in shapes if s.startswith("bf16")) == [
            "bf16[1,64,128,4096]", "bf16[1,64,192,4096]",
            "bf16[1,64,4096,192]"], shapes


def _latent_chunk_program(one_chip, monkeypatch):
    """The cell's compiled chunk program (64 slots x 4,608 latent rows, 1
    dense + 6 expert layers at the published widths) and its cache's
    shapes."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    monkeypatch.syspath_prepend(root)
    from benchmarks.adapters import serve_latent_moe
    from benchmarks.harness import manifest
    from benchmarks.references import kimi_k2
    from cloud_tpu.models import generation

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = manifest.Cell("kimi-k2-ep32-stage.agent-saturated", root=root)
    sizes, engine = cell.config, cell.traffic["engine"]
    config = serve_latent_moe.model_config(sizes, cell.traffic)
    sample = generation.SampleConfig(temperature=0.0)
    rows = engine["prompt_buckets"][-1] + engine["max_new_tokens"]

    on_chip = functools.partial(_tree_spec, one_chip)
    params = on_chip(kimi_k2.params_shape(sizes))
    cache = on_chip(jax.eval_shape(lambda: generation.init_slot_cache(
        config, engine["num_slots"], rows)))
    state = on_chip(jax.eval_shape(lambda: generation.init_slot_state(
        config, engine["num_slots"], sample=sample)))

    def fn(params, cache, state, rng):
        return generation.decode_chunk_program(
            params, cache, state, config, chunk_size=engine["chunk_tokens"],
            sample=sample, rng=rng)

    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, cache, state, _spec((2,), jnp.uint32, one_chip)).compile()
    return compiled, cache


#: A scatter onto an activation of the latent model's width, [n, 7168]:
#: how the dropless expert layer once put its rows back on their tokens,
#: one row after another.
ACTIVATION_SCATTER = re.compile(r" = \w+\[\d+,7168\]\S* scatter\(")


@pytest.mark.parametrize("program", ["chunk", "insert-4096"])
def test_latent_expert_layers_place_their_rows_without_a_scatter(
        one_chip, monkeypatch, program):
    """Neither of the cell's programs scatters onto an ``[n, 7168]``
    activation: an expert layer's rows reach their tokens through a
    product with the block's placement matrix (``moe._placed``).  The
    chunk program's one row-wise scatter left is the cache write's, onto
    the latent leaf."""
    if program == "chunk":
        text = _latent_chunk_program(one_chip, monkeypatch)[0].as_text()
        assert re.search(r" = bf16\[7,64,4608,640\]\S* scatter\(", text)
    else:
        text = _latent_prefill_text(one_chip, monkeypatch)
    assert "%grouped_matmul" in text
    found = [ln.strip()[:160] for ln in text.splitlines()
             if ACTIVATION_SCATTER.search(ln)]
    assert not found, found


def test_latent_expert_decode_chunk_copies_neither_cache_nor_experts(
        one_chip, monkeypatch):
    """The cell's chunk program (64 slots x 4,608 latent rows, 1 dense +
    6 expert layers at the published widths): the latent leaf is carried
    in place (no copy of it or of a layer of it), the routed experts'
    stacks reach the grouped products whole (no ``bf16[12,7168,2048]``
    slice: each was a 352 MB copy a call when the scan sliced them), and
    the kernels are there: the latent read of either stack and an expert
    layer's three grouped products."""
    compiled, cache = _latent_chunk_program(one_chip, monkeypatch)
    assert cache["latent"].shape == (7, 64, 4608, 640)
    text = compiled.as_text()
    names = re.findall(r"(%[\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    kinds = sorted(re.sub(r"\.\d+$", "", name) for name in names)
    assert kinds == ["%grouped_matmul_decode"] * 3 + ["%latent_decode"] * 2
    for made in ("bf16[12,7168,2048]", "bf16[12,2048,7168]",
                 "bf16[64,4608,640]"):
        assert made not in text, made
    # The leaf is never copied, re-made by an update of its own or put in
    # a fresh buffer (the row scatter is a fusion over the donated leaf).
    leaf = re.escape("bf16[7,64,4608,640]")
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%[\w.\-]+ = " + leaf
                         + r"\S* (copy|dynamic-update-slice|custom-call)\(",
                         ln)]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes <= 0.5e9


# ---------------------------------------------------------------------------
# Projections into heads: one plain dot on the stored kernel, split into
# heads after it (``layers.dense_heads_apply``)
# ---------------------------------------------------------------------------


def _assert_projections_read_in_place(text, kernel):
    """No ``copy`` takes a stacked ``params`` projection kernel whose name
    matches ``kernel`` (a transposed copy of the whole stack per program
    execution), and no projection product of ``attn_proj`` is a
    ``window={size=`` convolution (the head split folded into the product:
    the layout that asks for that copy, and stages each layer's slice in
    fast memory)."""
    copied = [ln.strip()[:160] for ln in text.splitlines()
              if re.search(r" copy\(%params\S*____att____" + kernel
                           + r"____kernel", ln)]
    assert not copied, copied
    folded = [ln.strip()[:160] for ln in text.splitlines()
              if re.search(r" convolution\(.*window=\{size=.*"
                           r"attn_proj/\.\.\.i,io->\.\.\.o/", ln)]
    assert not folded, folded


@pytest.mark.parametrize("program", ["chunk", "insert-512"])
def test_qkv_projections_read_the_stacked_kernels_in_place(
        one_chip, monkeypatch, program):
    """A decoder at baichuan-7b-l16's widths (4096, 32 heads of 128; 4
    layers), its chunk program at chat-open's grid (12 slots x 640 rows)
    and its insert at the 512 bucket: the q/k/v products are dots whose
    operand is the stacked kernel sliced in place, as ``attn_out``'s is.
    With the head split folded into them each was a convolution, and the
    chunk program copied all three stacks transposed, ``bf16[L,4096,4096]``
    each, once an execution (all of its temp)."""
    from cloud_tpu.models import generation, transformer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, rows = 12, 640
    config = transformer.TransformerConfig(
        vocab_size=64000, num_layers=4, dim=4096, num_heads=32,
        head_dim=128, mlp_hidden=11008, max_seq_len=rows, remat=False,
    )
    sample = generation.SampleConfig(temperature=0.0)
    on_chip = functools.partial(_tree_spec, one_chip)
    params = on_chip(jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), config)),
        jnp.bfloat16)
    cache = on_chip(jax.eval_shape(
        lambda: generation.init_slot_cache(config, slots, rows)))
    state = on_chip(jax.eval_shape(lambda: generation.init_slot_state(
        config, slots, sample=sample)))
    scalar = _spec((), jnp.int32, one_chip)
    if program == "chunk":
        def fn(params, cache, state):
            return generation.decode_chunk_program(
                params, cache, state, config, chunk_size=8, sample=sample)
        args = (params, cache, state)
    else:
        def fn(params, cache, state, tokens, prompt_len, slot, new):
            return generation.insert_slot_program(
                params, cache, state, tokens, prompt_len, slot, new, config,
                sample=sample)
        args = (params, cache, state, _spec((1, 512), jnp.int32, one_chip),
                scalar, scalar, scalar)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
    text = compiled.as_text()
    assert "____att____q____kernel" in text
    _assert_projections_read_in_place(text, r"(?:q|k|v)")
    if program == "chunk":
        assert compiled.memory_analysis().temp_size_in_bytes <= 0.05e9


def test_latent_query_projection_reads_its_stacked_kernel_in_place(
        one_chip, monkeypatch):
    """The latent queries' up-projection ``q_b`` at kimi-k2-ep32-stage's
    widths (7168 -> rank 1536 -> 64 heads of 192; 2 dense layers), in the
    chunk program at the cell's 64 slots: a dot on the stacked kernel
    sliced in place, not a convolution over a transposed copy of the
    stack (0.23 GB at the cell's 6 expert layers)."""
    from cloud_tpu.models import generation, mla, transformer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, rows = 64, 1024
    config = transformer.TransformerConfig(
        vocab_size=20480, num_layers=2, dim=7168, num_heads=64,
        head_dim=128, mlp_hidden=2048, max_seq_len=rows, remat=False,
        latent=mla.LatentConfig(),
    )
    sample = generation.SampleConfig(temperature=0.0)
    on_chip = functools.partial(_tree_spec, one_chip)
    params = on_chip(jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), config)),
        jnp.bfloat16)
    cache = on_chip(jax.eval_shape(
        lambda: generation.init_slot_cache(config, slots, rows)))
    state = on_chip(jax.eval_shape(lambda: generation.init_slot_state(
        config, slots, sample=sample)))

    def fn(params, cache, state):
        return generation.decode_chunk_program(
            params, cache, state, config, chunk_size=8, sample=sample)

    text = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, cache, state).compile().as_text()
    assert "____att____q_b____kernel" in text and "%latent_decode" in text
    _assert_projections_read_in_place(text, "q_b")
