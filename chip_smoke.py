"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, in ONE process, through the entry points a
user calls, at the published widths of the models (weights are random,
made from ``--seed``):

* *train* — ``Trainer.fit`` on ResNet50 (``resnet.RESNET50_CIFAR``, b256,
  bf16, synthetic data) under the mesh ``core.bootstrap`` would install:
  finite, non-increasing loss; the fused GroupNorm kernel traced and in
  the compiled step; one GroupNorm fwd+grad against the jnp reference.
* *serve* — ``ServingEngine`` on ``transformer.SMALL`` (bf16) with the
  default continuous scheduler and a prompt bucket that reaches the flash
  kernel (1024): greedy tokens equal ``generation.generate`` for every
  request — or, where bf16 arithmetic lets two near-tied tokens swap,
  BOTH continuations are shown greedy by teacher forcing through the full
  forward pass — then the same requests through ``decode_kernel="pallas"``.

``--chips 4`` runs ONLY the multi-chip phase and what it is compared with:
the ResNet50 trainer under a ``dp=4`` mesh vs one device, and a ``tp=4``
serving slice (default and ``decode_kernel="pallas"``) vs ``tp=1``.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it.  Anything else — no TPU, a failed assertion, an
exception in any phase — ends the run with a non-zero exit and no such
line; nothing is caught and survived.  ``--tiny`` is the CPU rehearsal
(tiny models, kernels through the Pallas interpreter): it walks the same
code and then still fails, because the platform is not ``tpu``.

Numbers printed on the way are stamped "smoke, not a benchmark".
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import cloud_tpu.ops  # noqa: F401  (binds the kernel modules)
from cloud_tpu import parallel
from cloud_tpu.models import generation, resnet, transformer
from cloud_tpu.monitoring import metrics, tracing
from cloud_tpu.parallel import planner
from cloud_tpu.serving import ServeConfig, ServingEngine
from cloud_tpu.training import compile_cache, data, trainer
from cloud_tpu.training import train as train_lib

REPO = os.path.dirname(os.path.abspath(__file__))

gn = sys.modules["cloud_tpu.ops.group_norm"]
fa = sys.modules["cloud_tpu.ops.flash_attention"]
pa = sys.modules["cloud_tpu.ops.paged_attention"]


@dataclasses.dataclass(frozen=True)
class Sizes:
    resnet: resnet.ResNetConfig
    steps: int  # train steps per Trainer.fit
    batch: int
    image: int
    gn_check_shape: tuple
    lm: transformer.TransformerConfig
    buckets: tuple
    new_tokens: int
    slots: int
    prompt_lens: tuple
    forward_pad: int  # teacher-forcing width = prompt bucket + this


FULL = Sizes(
    resnet=resnet.RESNET50_CIFAR, steps=6, batch=256, image=32,
    gn_check_shape=(8, 16, 16, 512),
    # Widths as published; only the position table grows, so that a slot
    # row holds the 1024 bucket plus the new tokens.
    lm=transformer.SMALL.scaled(max_seq_len=2048),
    buckets=(128, 1024), new_tokens=16, slots=4,
    prompt_lens=(5, 40, 100, 128, 700, 1024), forward_pad=128,
)
TINY = Sizes(
    resnet=resnet.RESNET8_CIFAR, steps=3, batch=8, image=8,
    gn_check_shape=(8, 8, 8, 64),
    lm=transformer.TINY, buckets=(16, 64), new_tokens=4, slots=2,
    prompt_lens=(3, 16, 40, 64), forward_pad=8,
)


#: How far below a position's best logit a greedy token may lie and still
#: count as a tie, in units of that position's logit std: 4 units of bf16
#: roundoff (2**-8).  Two correct bf16 evaluations of one network differ by
#: a few roundoffs, so a best and a second-best token this close may swap
#: (the widest swap seen on the chip: 0.0093).  The typical gap between a
#: position's best two of 32k near-flat logits is about 0.2 std, so a true
#: second-best passes at one position in fourteen or so, a random token
#: (about four std down) never — and every token of a request has to pass.
TIE = 2.0 ** -6


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(ok, message):
    # Not ``assert``: the verdict must not depend on ``python -O``.
    if not ok:
        raise SmokeFailure(message)


def _parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the multi-chip phase and its "
                        "one-chip comparison")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal at tiny sizes (never ok)")
    return parser.parse_args(argv)


class _StepLog(trainer.Callback):
    """Per-step loss (host-read, so each step is waited for) and time."""

    def __init__(self):
        self.losses, self.times = [], []

    def on_step_end(self, step, logs, trainer_):
        self.losses.append(float(logs["loss"]))
        self.times.append(time.perf_counter())


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))


class Smoke:
    """One run: its arguments, the device as JAX reports it, the sizes."""

    def __init__(self, args):
        self.args = args
        self.device = {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }
        self.on_tpu = self.device["platform"] == "tpu"
        self.sizes = TINY if args.tiny else FULL
        self._forward = jax.jit(
            lambda params, tokens: transformer.apply(
                params, tokens, self.sizes.lm, mesh=None)[0])

    def say(self, msg):
        print("[{platform} / {kind} / {count}] ".format(**self.device) + msg,
              flush=True)

    def verdict(self, ok, **more):
        print(json.dumps({"ok": ok, "device": self.device, **more}),
              flush=True)
        return 0 if ok else 1

    # -- train ---------------------------------------------------------

    @functools.cached_property
    def _batch(self):
        """The one synthetic batch every train step of this run sees."""
        z = self.sizes
        rng = np.random.default_rng(self.args.seed)
        labels = rng.integers(0, z.resnet.num_classes, z.batch)
        images = rng.normal(size=(z.batch, z.image, z.image, 3))
        return {"image": images.astype(np.float32),
                "label": labels.astype(np.int32)}

    def _train_data(self):
        """The batch met ``steps`` times: on a batch it has seen the
        loss must not rise, which fresh noise would not promise."""
        steps = self.sizes.steps
        return data.ArrayDataset(
            {"image": np.tile(self._batch["image"], (steps, 1, 1, 1)),
             "label": np.tile(self._batch["label"], steps)},
            self.sizes.batch,
        )

    def _fit_resnet(self, mesh):
        """What a user script does after ``core.bootstrap`` installed
        ``mesh``: a Trainer over the global mesh, ``init_state``, ``fit``."""
        config = self.sizes.resnet
        t = trainer.Trainer(
            functools.partial(resnet.loss_fn, config=config, mesh=mesh),
            optax.sgd(1e-3, momentum=0.9),
            functools.partial(resnet.init, config=config),
            mesh=mesh,
            logical_axes=(
                resnet.param_logical_axes(config) if mesh is not None
                else None
            ),
        )
        t.init_state(jax.random.PRNGKey(self.args.seed))
        log = _StepLog()
        start = time.perf_counter()
        t.fit(self._train_data(), epochs=1, callbacks=[log])
        check(len(log.losses) == self.sizes.steps, f"steps run: {log.losses}")
        check(all(np.isfinite(log.losses)), f"non-finite loss: {log.losses}")
        return t, log, start

    def _step_text(self, t):
        """The compiled text of the step ``t.fit`` dispatched."""
        return t.lower_train_step(self._batch).compile().as_text()

    def _check_group_norm_against_reference(self):
        shape = self.sizes.gn_check_shape
        keys = jax.random.split(jax.random.PRNGKey(self.args.seed + 3), 3)
        x = jax.random.normal(keys[0], shape, jnp.bfloat16) * 2.0 + 5.0
        scale = jax.random.normal(keys[1], shape[-1:], jnp.float32) * 0.2 + 1
        bias = jnp.zeros(shape[-1:], jnp.float32)
        res = jax.random.normal(keys[2], shape, jnp.bfloat16)

        def kernel(x, scale, bias, res):
            return gn.group_norm(
                x, scale, bias, num_groups=32, use_pallas=True,
                partitioned=False, activation="relu", residual=res)

        def reference(x, scale, bias, res):
            return gn._reference(x, scale, bias, 32, relu=True, residual=res)

        def value_and_grads(fn):
            def loss(*operands):
                return jnp.sum(fn(*operands).astype(jnp.float32) ** 2)

            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(
                x, scale, bias, res)

        got, want = value_and_grads(kernel), value_and_grads(reference)
        errs = [_rel_err(got[0], want[0])] + [
            _rel_err(g, w) for g, w in zip(got[1], want[1])
        ]
        check(max(errs) < 3e-2, f"GroupNorm kernel vs reference: {errs}")
        self.say(f"group_norm fwd+grad vs _reference at {shape} bf16: "
                 f"max rel err {max(errs):.2e} (< 3e-2)")

    def train_phase(self):
        z = self.sizes
        self._check_group_norm_against_reference()
        # As bootstrap does: plan a mesh over the local devices, install it.
        mesh = planner.plan_mesh(num_devices=1).build(jax.devices()[:1])
        traced = gn.KERNEL_TRACE_COUNT
        with parallel.use_mesh(mesh):
            t, log, start = self._fit_resnet(parallel.get_global_mesh())
            text = self._step_text(t)
        check(gn.KERNEL_TRACE_COUNT > traced, "GroupNorm kernel never traced")
        if self.on_tpu:
            check("tpu_custom_call" in text,
                  "no Pallas kernel in the compiled train step")
        first, last = log.losses[0], log.losses[-1]
        check(last <= first * 1.05, f"loss rose: {log.losses}")
        steady = (len(log.times) - 1) / (log.times[-1] - log.times[0])
        self.say(
            f"train: ResNet50 {z.resnet.stage_sizes} w{z.resnet.width} "
            f"b{z.batch} {z.image}x{z.image} bf16, {z.steps} steps "
            f"via Trainer.fit; losses {np.round(log.losses, 4).tolist()}; "
            f"group_norm kernel traces +{gn.KERNEL_TRACE_COUNT - traced}; "
            "tpu_custom_call in step: "
            f"{text.count('tpu_custom_call') if self.on_tpu else 'n/a off-TPU'}"
        )
        self.say(
            f"train: fit start to first step done {log.times[0] - start:.2f}s"
            f" (compile included); then {steady:.2f} steps/s "
            "(smoke, not a benchmark)")

    # -- serve ---------------------------------------------------------

    def _prompts(self):
        rng = np.random.default_rng(self.args.seed)
        return [rng.integers(1, self.sizes.lm.vocab_size, n).astype(np.int32)
                for n in self.sizes.prompt_lens]

    def _generate_reference(self, params, prompts):
        """Per-request greedy ``generate()`` (prompts padded to their
        bucket, so two shapes compile, not one per length)."""
        z, want = self.sizes, []
        for prompt in prompts:
            bucket = next(b for b in z.buckets if len(prompt) <= b)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(prompt)] = prompt
            out = generation.generate(
                params, jnp.asarray(padded),
                jnp.asarray([len(prompt)], np.int32), z.lm,
                max_new_tokens=z.new_tokens,
                sample=generation.SampleConfig(temperature=0.0), mesh=None,
            )
            want.append(np.asarray(out["tokens"])[0])
        return want

    def _serve(self, params, prompts, **serve_kw):
        z = self.sizes
        serve = ServeConfig(
            max_new_tokens=z.new_tokens, prompt_buckets=z.buckets,
            num_slots=z.slots, warmup=True, **serve_kw,
        )
        start = time.perf_counter()
        with ServingEngine(params, z.lm, serve, mesh=None) as engine:
            engine.wait_ready()
            ready = time.perf_counter()
            futures = [engine.submit(p) for p in prompts]
            results = [f.result(timeout=600) for f in futures]
            done = time.perf_counter()
            placement = engine.placement()
        tokens = [np.asarray(r.tokens) for r in results]
        rate = sum(int(r.num_generated) for r in results) / (done - ready)
        return tokens, ready - start, rate, placement

    def _forced_margins(self, params, prompt, tokens):
        """Teacher forcing through the plain full-sequence forward pass
        (``transformer.apply``: no KV cache, no slot grid): how far each
        generated token's logit lies below its position's best, in units
        of that position's logit std."""
        z = self.sizes
        width = next(b for b in z.buckets if len(prompt) <= b) + z.forward_pad
        padded = np.zeros((1, width), np.int32)  # causal: the tail is inert
        padded[0, :len(prompt) + len(tokens)] = np.concatenate(
            [prompt, tokens])
        logits = np.asarray(self._forward(params, jnp.asarray(padded)))[0]
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
        chosen = rows[np.arange(len(tokens)), tokens]
        return (rows.max(axis=-1) - chosen) / rows.std(axis=-1)

    def _check_greedy(self, what, params, prompts, got, want):
        """``got`` must be ``want`` token for token — or, where a request
        leaves it, BOTH must be greedy all the same: every token of either
        the best of its own position (to within ``TIE``) under teacher
        forcing, so that the two are shown to part at a near-tie."""
        ties = []
        for prompt, g, w in zip(prompts, got, want):
            if np.array_equal(g, w):
                continue
            at = int(np.argmax(g != w))
            sides = {"its": self._forced_margins(params, prompt, g),
                     "the reference's": self._forced_margins(params, prompt, w)}
            for whose, margins in sides.items():
                check(margins.max() <= TIE,
                      f"{what}: prompt of {len(prompt)} tokens leaves the "
                      f"reference at token {at} and {whose} tokens are not "
                      f"greedy: margins {np.round(margins, 4).tolist()} "
                      f"(> {TIE}); {g} vs {w}")
            # Up to ``at`` both saw one prefix: row ``at`` is one row of
            # logits, and these are the two tokens' distances to its best.
            below = " / ".join(f"{m[at]:.4f}" for m in sides.values())
            ties.append(f"prompt {len(prompt)} at token {at} (the two "
                        f"tokens lie {below} std below that position's best)")
        same = len(prompts) - len(ties)
        return (f"identical on {same} of {len(prompts)} requests"
                + (f"; near-tie divergence, both sides greedy under teacher "
                   f"forcing to within {TIE} std: {ties}" if ties else ""))

    def serve_phase(self):
        z = self.sizes
        params = transformer.init(jax.random.PRNGKey(self.args.seed), z.lm)
        prompts = self._prompts()
        flash_traced = fa.KERNEL_TRACE_COUNT
        tokens, warm_s, rate, _ = self._serve(params, prompts)
        check(fa.KERNEL_TRACE_COUNT > flash_traced,
              f"flash kernel never traced at bucket {z.buckets[-1]}")
        self.say(
            f"serve: SMALL L{z.lm.num_layers} d{z.lm.dim} h{z.lm.num_heads} "
            f"bf16, buckets {z.buckets}, {z.slots} slots, continuous; engine "
            f"warm-up {warm_s:.2f}s (compiles included); prompts "
            f"{z.prompt_lens} x {z.new_tokens} new tokens at {rate:.1f} "
            "tokens/s (smoke, not a benchmark); flash kernel traces "
            f"+{fa.KERNEL_TRACE_COUNT - flash_traced}")
        want = self._generate_reference(params, prompts)
        self.say("serve: greedy tokens vs generate(): " + self._check_greedy(
            "engine vs generate()", params, prompts, tokens, want))
        paged_traced = pa.KERNEL_TRACE_COUNT
        tokens_k, warm_s, rate, _ = self._serve(params, prompts,
                                                decode_kernel="pallas")
        check(pa.KERNEL_TRACE_COUNT > paged_traced,
              "paged kernel never traced")
        self.say(
            'serve: decode_kernel="pallas" vs generate(): '
            + self._check_greedy('decode_kernel="pallas" vs generate()',
                                 params, prompts, tokens_k, want)
            + f"; warm-up {warm_s:.2f}s; {rate:.1f} tokens/s (smoke, not a "
            "benchmark); paged kernel traces "
            f"+{pa.KERNEL_TRACE_COUNT - paged_traced}")

    # -- four chips ----------------------------------------------------

    def multichip_train_phase(self):
        """ResNet50 Trainer under a dp=4 global mesh vs the same global
        batch on one device."""
        z = self.sizes
        _, log1, _ = self._fit_resnet(None)
        mesh = parallel.MeshSpec({"dp": 4}).build(jax.devices()[:4])
        traced = gn.KERNEL_TRACE_COUNT
        with parallel.use_mesh(mesh):
            t4, log4, _ = self._fit_resnet(parallel.get_global_mesh())
            text = self._step_text(t4)
            image = train_lib.shard_batch(
                self._batch, mesh, t4.rules)["image"]
        check(gn.KERNEL_TRACE_COUNT > traced, "GroupNorm kernel never traced")
        kernel_lines = [ln for ln in text.splitlines()
                        if "tpu_custom_call" in ln and "bf16[" in ln]
        if self.on_tpu:
            check(kernel_lines and all(f"bf16[{z.batch // 4}," in ln
                                       for ln in kernel_lines),
                  "GroupNorm kernel is not on a quarter of the batch")
        check(np.allclose(log4.losses, log1.losses, rtol=3e-2, atol=3e-2),
              f"dp=4 losses {log4.losses} vs one device {log1.losses}")
        param_sets = {len(x.sharding.device_set)
                      for x in jax.tree_util.tree_leaves(t4.state.params)}
        check(param_sets == {4}, f"param device sets: {param_sets}")
        shards = {tuple(s.data.shape) for s in image.addressable_shards}
        check(shards == {(z.batch // 4, z.image, z.image, 3)}, str(shards))
        check(len({s.device for s in image.addressable_shards}) == 4,
              "batch shards share a device")
        self.say(
            f"train dp=4: losses {np.round(log4.losses, 4).tolist()} vs one "
            f"device {np.round(log1.losses, 4).tolist()} (rtol 3e-2); every "
            f"param on {param_sets} devices; batch split into {shards} on 4 "
            "devices; group_norm kernel traces "
            f"+{gn.KERNEL_TRACE_COUNT - traced}, per-shard kernel calls in "
            f"step: {len(kernel_lines) if self.on_tpu else 'n/a off-TPU'}")

    def multichip_serve_phase(self):
        """A tp=4 serving slice vs tp=1: same greedy tokens, KV over heads,
        the flash and (``decode_kernel="pallas"``) paged kernels per head
        shard."""
        z = self.sizes
        params = transformer.init(jax.random.PRNGKey(self.args.seed), z.lm)
        prompts = self._prompts()
        tokens1, _, _, place1 = self._serve(params, prompts)
        flash_traced = fa.KERNEL_TRACE_COUNT
        tokens4, _, _, place4 = self._serve(params, prompts,
                                            mesh_shape=(4, 1))
        check(fa.KERNEL_TRACE_COUNT > flash_traced,
              f"tp=4: flash kernel never traced at bucket {z.buckets[-1]}")
        parity = self._check_greedy("tp=4 vs tp=1", params, prompts,
                                    tokens4, tokens1)
        check(len(place4["kv_devices"]) == 4, str(place4))
        heads = {s[-2] for s in place4["kv_shard_shapes"]}
        check(heads == {z.lm.num_heads // 4}, str(place4))
        self.say(
            f"serve tp=4: greedy tokens vs tp=1: {parity}; KV leaves "
            f"{place4['kv_shapes']} in shards {place4['kv_shard_shapes']} "
            f"on devices {place4['kv_devices']}; params on devices "
            f"{place4['param_devices']} (tp=1: "
            f"{place1['param_devices']}); flash kernel traces "
            f"+{fa.KERNEL_TRACE_COUNT - flash_traced}")
        paged_traced = pa.KERNEL_TRACE_COUNT
        tokens4k, _, _, _ = self._serve(params, prompts, mesh_shape=(4, 1),
                                        decode_kernel="pallas")
        check(pa.KERNEL_TRACE_COUNT > paged_traced,
              "tp=4: paged kernel never traced")
        self.say(
            'serve tp=4 decode_kernel="pallas": greedy tokens vs tp=1: '
            + self._check_greedy('tp=4 decode_kernel="pallas" vs tp=1',
                                 params, prompts, tokens4k, tokens1)
            + f"; paged kernel traces +{pa.KERNEL_TRACE_COUNT - paged_traced}")

        # Where a 2-replica Fleet's parameters land (printed, not checked:
        # every engine builds its mesh from jax.devices()[:chips] — S3).
        from cloud_tpu.fleet import Fleet, FleetConfig

        serve = ServeConfig(max_new_tokens=z.new_tokens,
                            prompt_buckets=z.buckets[:1], num_slots=z.slots)
        with Fleet(lambda: ServingEngine(params, z.lm, serve, mesh=None),
                   FleetConfig(min_replicas=2)) as fleet:
            landed = [r.engine.placement()["param_devices"]
                      for r in fleet.replicas()]
        self.say(f"fleet of 2 replicas: params on device ids {landed} "
                 "(ROADMAP S3: replicas are not spread over chips yet)")

    # -- the run -------------------------------------------------------

    def run(self):
        if not self.on_tpu and not self.args.tiny:
            return self.verdict(False, error="no TPU: chip_smoke.py proves "
                                "the chip path and runs nowhere else")
        if self.device["count"] < self.args.chips:
            return self.verdict(
                False, error=f"--chips {self.args.chips} needs that many")
        # JAX_COMPILATION_CACHE_DIR where it is set, else one fixed path in
        # the checkout (the directory is part of the cache key).
        compile_cache.maybe_enable_persistent_cache(
            os.path.join(REPO, ".jax_cache"))
        tracing.enable()
        self.say(f"chip_smoke seed {self.args.seed}; compile cache at "
                 f"{jax.config.jax_compilation_cache_dir}; metrics registry: "
                 f"{metrics.backend()}")
        phases = (
            (self.train_phase, self.serve_phase) if self.args.chips == 1
            else (self.multichip_train_phase, self.multichip_serve_phase)
        )
        for phase in phases:
            start = time.perf_counter()
            phase()
            self.say(f"{phase.__name__} passed in "
                     f"{time.perf_counter() - start:.1f}s")
        compiles = {
            name: round(agg["total_seconds"], 2)
            for name, agg in sorted(tracing.aggregates().items())
            if name.startswith("compile/") or name == "step/first_compile"
        }
        self.say(f"compile seconds by span: {compiles}")
        stats = jax.devices()[0].memory_stats() or {}
        self.say("peak_bytes_in_use: "
                 f"{stats.get('peak_bytes_in_use', 'not reported')}")
        if not self.on_tpu:
            return self.verdict(False, error="rehearsal reached its end, "
                                "but the platform is not tpu")
        return self.verdict(True)


def main(argv=None):
    args = _parse(sys.argv[1:] if argv is None else argv)
    if args.tiny:
        # The rehearsal walks the kernel code through the Pallas
        # interpreter (read by ops.dispatch at every call).
        os.environ["CLOUD_TPU_FLASH_FORCE_INTERPRET"] = "1"
    return Smoke(args).run()


if __name__ == "__main__":
    sys.exit(main())
