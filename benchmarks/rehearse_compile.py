"""Compile a cell's programs at their real size for a DESCRIBED v5e, with
the kernels on — run by hand, here, before a chip call (no chip needed;
``on-chip-measurement`` section 2.3).  Not a test file: the repo keeps its
one libtpu-loading test file elsewhere.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile.py \
        [--root tests/benchmarks/proposed] <cell> [...]

Prints, for each program, the compiler's bytes (arguments, outputs, temp)
and whether a Pallas kernel (``tpu_custom_call``) is in it.  A compile that
passes is not a chip run.
"""

import argparse
import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.harness import manifest  # noqa: E402

# Auto-dispatch asks the default backend; steer it here, in the script.
jax.default_backend = lambda: "tpu"


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _report(label, lowered):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    print(f"{label}: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"outputs {mem.output_size_in_bytes / 1e9:.2f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, alias "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB; tpu_custom_call x"
          f"{kernels}", flush=True)


def train_fit(cell, chip):
    from benchmarks.references import resnet_gn
    from cloud_tpu.models import resnet
    from cloud_tpu.training import train as train_lib

    sizes, mix = cell.config, cell.traffic
    config = resnet.ResNetConfig(
        stage_sizes=tuple(sizes["stage_sizes"]), width=sizes["width"],
        num_classes=sizes["num_classes"], num_groups=sizes["num_groups"],
        dtype=jnp.dtype(sizes["compute_dtype"]))
    optimizer = optax.sgd(mix["optimizer"]["learning_rate"],
                          momentum=mix["optimizer"]["momentum"])

    def build(key):
        params = resnet_gn.init_params(key, sizes)
        return train_lib.TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=optimizer.init(params))

    state = _on(chip, jax.eval_shape(build, jax.random.PRNGKey(0)))
    b, s = mix["batch_size"], mix["image_size"]
    batch = {"image": jax.ShapeDtypeStruct((b, s, s, 3), jnp.float32,
                                           sharding=chip),
             "label": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=chip)}
    step = train_lib.make_train_step(
        functools.partial(resnet.loss_fn, config=config, mesh=None),
        optimizer)
    _report(f"{cell.name} train step b{b} {s}x{s}", step.lower(state, batch))


def serve_engine(cell, chip):
    from benchmarks.adapters import serve_engine as adapter
    from benchmarks.references import decoder
    from cloud_tpu.models import generation

    sizes, mix = cell.config, cell.traffic
    config = adapter.model_config(sizes, mix)
    engine = mix["engine"]
    sample = generation.SampleConfig(temperature=0.0)
    params = _on(chip, decoder.params_shape(sizes))
    rows = engine["prompt_buckets"][-1] + engine["max_new_tokens"]
    cache = _on(chip, jax.eval_shape(
        lambda: generation.init_slot_cache(config, engine["num_slots"],
                                           rows)))
    state = _on(chip, jax.eval_shape(
        lambda: generation.init_slot_state(config, engine["num_slots"],
                                           sample=sample)))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)

    def chunk(params, cache, state, rng):
        return generation.decode_chunk_program(
            params, cache, state, config, chunk_size=engine["chunk_tokens"],
            sample=sample, rng=rng, mesh=None)

    _report(f"{cell.name} decode chunk, {engine['num_slots']} slots x "
            f"{rows} rows",
            jax.jit(chunk, donate_argnums=(1, 2)).lower(
                params, cache, state, rng))
    for bucket in engine["prompt_buckets"]:
        tokens = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip)

        def insert(params, cache, state, tokens, n, slot, new, rng):
            return generation.insert_slot_program(
                params, cache, state, tokens, n, slot, new, config,
                sample=sample, rng=rng, mesh=None)

        _report(f"{cell.name} insert at bucket {bucket}",
                jax.jit(insert, donate_argnums=(1, 2)).lower(
                    params, cache, state, tokens, scalar, scalar, scalar,
                    rng))


def main(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("cells", nargs="+")
    args = parser.parse_args(argv)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in args.cells:
        cell = manifest.Cell(name, root=args.root)
        {"train_fit": train_fit, "serve_engine": serve_engine}[
            cell.config["entry"]](cell, chip)


if __name__ == "__main__":
    main(sys.argv[1:])
