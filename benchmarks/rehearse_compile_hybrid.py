"""``rehearse_compile.py`` for a cell whose entry is ``serve_hybrid`` (that
script dispatches on two entries by name): the chunk program and every
insert at the cell's real sizes, compiled for a DESCRIBED v5e — run by
hand, here, before a chip call.  The depth rule of
``configs/falcon-h1-34b-stage.json`` reads its numbers: the weights, the
slot cache and the largest program's temp must fit 15.0 GB.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile_hybrid.py <cell>

A compile that passes is not a chip run.
"""

import sys

from rehearse_compile import ROOT, _on, _report  # sets the backend too

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.adapters import serve_hybrid  # noqa: E402
from benchmarks.harness import manifest  # noqa: E402
from benchmarks.references import falcon_h1  # noqa: E402
from cloud_tpu.models import generation  # noqa: E402


def main(cells):
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in cells:
        cell = manifest.Cell(name, root=ROOT)
        sizes, engine = cell.config, cell.traffic["engine"]
        config = serve_hybrid.model_config(sizes, cell.traffic)
        sample = generation.SampleConfig(temperature=0.0)
        params = _on(chip, falcon_h1.params_shape(sizes))
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
