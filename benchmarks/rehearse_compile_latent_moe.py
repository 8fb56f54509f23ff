"""``rehearse_compile.py`` for a cell whose entry is ``serve_latent_moe``:
the weights' making, the chunk program and every insert at the cell's real
sizes, compiled for a DESCRIBED v5e — run by hand, here, before a chip
call.  The depth rule of ``configs/kimi-k2-ep32-stage.json`` reads its
numbers: the weights, the slot cache and the largest program's temp must
fit 15.0 GB.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile_latent_moe.py <cell>

A compile that passes is not a chip run.
"""

import importlib
import sys

from rehearse_compile import ROOT, _on, _report  # sets the backend too

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.adapters import serve_latent_moe  # noqa: E402
from benchmarks.harness import manifest  # noqa: E402
from cloud_tpu.models import generation  # noqa: E402


def main(cells):
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in cells:
        cell = manifest.Cell(name, root=ROOT)
        sizes, engine = cell.config, cell.traffic["engine"]
        reference = importlib.import_module(
            f"benchmarks.references.{sizes['reference']}")
        config = serve_latent_moe.model_config(sizes, cell.traffic)
        sample = generation.SampleConfig(temperature=0.0)
        params = _on(chip, reference.params_shape(sizes))
        rows = engine["prompt_buckets"][-1] + engine["max_new_tokens"]
        cache = _on(chip, jax.eval_shape(
            lambda: generation.init_slot_cache(config, engine["num_slots"],
                                               rows)))
        state = _on(chip, jax.eval_shape(
            lambda: generation.init_slot_state(config, engine["num_slots"],
                                               sample=sample)))
        held = sum(x.size * x.dtype.itemsize for x in
                   jax.tree_util.tree_leaves((params, cache)))
        print(f"{name}: weights and slot cache {held / 1e9:.2f} GB",
              flush=True)
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
        keys = _on(chip, jax.eval_shape(lambda: reference._keys(0, sizes)))
        sizes_key = reference._sizes_key(sizes)
        _report(f"{name} the fit of the selection bias",
                reference._calibrate.lower(keys, sizes_key, jnp.bfloat16))
        biases = jax.ShapeDtypeStruct(
            (sizes["num_hidden_layers"] - sizes["first_k_dense_replace"],
             sizes["published"]["n_routed_experts"]), jnp.float32,
            sharding=chip)
        _report(f"{name} the weights' making",
                reference._build_params.lower(keys, biases, sizes_key,
                                              jnp.bfloat16))

        def chunk(params, cache, state, rng):
            return generation.decode_chunk_program(
                params, cache, state, config,
                chunk_size=engine["chunk_tokens"], sample=sample, rng=rng,
                mesh=None)

        _report(f"{name} decode chunk, {engine['num_slots']} slots x {rows} "
                "rows", jax.jit(chunk, donate_argnums=(1, 2)).lower(
                    params, cache, state, rng))
        for bucket in engine["prompt_buckets"]:
            tokens = jax.ShapeDtypeStruct((1, bucket), jnp.int32,
                                          sharding=chip)

            def insert(params, cache, state, tokens, n, slot, new, rng):
                return generation.insert_slot_program(
                    params, cache, state, tokens, n, slot, new, config,
                    sample=sample, rng=rng, mesh=None)

            _report(f"{name} insert at bucket {bucket}",
                    jax.jit(insert, donate_argnums=(1, 2)).lower(
                        params, cache, state, tokens, scalar, scalar, scalar,
                        rng))


if __name__ == "__main__":
    main(sys.argv[1:])
