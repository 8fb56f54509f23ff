"""Program scopes (``layers.SCOPES``): every operation of the two slot
programs that does the model's work carries exactly one of them in its
optimized HLO metadata, and a scope is metadata alone.

One tiny configuration of each kind of slot cache (plain K/V rows, K/V rows
beside a recurrent state, latent rows with dropless experts), from the
benchmark's own configuration files, on the CPU.
"""

import contextlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.adapters import (serve_engine, serve_hybrid,  # noqa: E402
                                 serve_latent_moe)
from cloud_tpu.models import generation, layers, transformer  # noqa: E402

BUCKET, NEW, SLOTS, CHUNK = 16, 8, 3, 2
MIX = {"engine": {"prompt_buckets": [BUCKET], "max_new_tokens": NEW}}
GREEDY = generation.SampleConfig(temperature=0.0)
KINDS = {
    "kv": ("baichuan-7b-l16", serve_engine),
    "ssm": ("falcon-h1-34b-stage", serve_hybrid),
    "latent_moe": ("kimi-k2-ep32-stage", serve_latent_moe),
}
#: The operations that do a model's work whatever the compiler makes of the
#: rest: each, and each fusion rooted in one, must go by a scope.
HELD = ("dot", "custom-call", "scatter", "sort")


def _config(kind):
    name, adapter = KINDS[kind]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{name}.json")) as f:
        sizes = json.load(f)
    sizes = {**sizes, **sizes["tiny"]}
    return adapter.model_config(sizes, MIX).scaled(dtype=jnp.float32)


def _programs(config):
    """The two slot programs as jitted functions of arrays alone, and
    arguments for each."""
    params = transformer.init(jax.random.PRNGKey(0), config)
    cache = generation.init_slot_cache(config, SLOTS, BUCKET + NEW)
    state = generation.init_slot_state(config, SLOTS, sample=GREEDY)
    prompt = np.zeros((1, BUCKET), np.int32)
    prompt[0, :11] = np.arange(1, 12)

    def insert_fn(params, cache, state, prompt):
        return generation.insert_slot_program(
            params, cache, state, prompt, 11, 1, NEW, config, sample=GREEDY)

    def chunk_fn(params, cache, state):
        return generation.decode_chunk_program(
            params, cache, state, config, chunk_size=CHUNK, sample=GREEDY)

    return {"insert": (insert_fn, (params, cache, state, prompt)),
            "chunk": (chunk_fn, (params, cache, state))}


def _run(programs):
    """insert, then a chunk over what the insert left: every output."""
    insert_fn, (params, cache, state, prompt) = programs["insert"]
    inserted = jax.jit(insert_fn)(params, cache, state, prompt)
    chunked = jax.jit(programs["chunk"][0])(params, *inserted[:2])
    return jax.tree_util.tree_leaves((inserted, chunked))


_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%(?P<name>[^\s=]+) = (?P<rest>.*)$")
_KIND = re.compile(r"(?:^|[\s)}])([a-z][a-z\-]*)\(")


def _instructions(hlo_text):
    """[(computation, name, opcode, op_name, is root, called fusion
    computation)] of an optimized HLO module's text."""
    out, computation = [], None
    for line in hlo_text.splitlines():
        header = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$", line)
        if header:
            computation = header.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        kind = _KIND.search(m.group("rest"))
        op_name = re.search(r'op_name="([^"]*)"', line)
        calls = re.search(r"calls=%(\S+?)[,\s]", line + " ")
        out.append((computation, m.group("name"),
                    kind.group(1) if kind else "",
                    op_name.group(1) if op_name else "",
                    bool(m.group(1)), calls.group(1) if calls else None))
    return out


def _held(hlo_text):
    """``(named, bad)``: how many held operations of a module, and fusions
    rooted in one, have a name, and those of them that do not carry exactly
    one scope, as [(instruction, opcode, op_name)].  A ``dot`` with no name
    at all is the compiler's own (the CPU's rewrite of a batched product
    names nothing) and has nothing to hold."""
    instructions = _instructions(hlo_text)
    roots = {comp: (opcode, op_name)
             for comp, _, opcode, op_name, is_root, _ in instructions
             if is_root}
    named, bad = 0, []
    for _, name, opcode, op_name, _, calls in instructions:
        if opcode == "fusion":
            root_opcode, root_name = roots.get(calls, ("", ""))
            if root_opcode not in HELD:
                continue
            op_name = op_name or root_name
        elif opcode not in HELD:
            continue
        if opcode == "dot" and not op_name:
            continue
        named += 1
        scopes = [part for part in op_name.split("/")
                  if part in layers.SCOPES]
        if len(scopes) != 1:
            bad.append((name, opcode, op_name))
    return named, bad


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_held_operation_of_the_slot_programs_goes_by_one_scope(
        kind, monkeypatch):
    config = _config(kind)
    programs = _programs(config)
    for name, (fn, args) in programs.items():
        compiled = jax.jit(fn).lower(*args).compile()
        named, bad = _held(compiled.as_text())
        assert named >= 8 and not bad, (
            f"{kind} {name}: {named} named, no one scope on {bad}")
    scoped = _run(programs)
    jaxprs = {name: str(jax.make_jaxpr(fn)(*args))
              for name, (fn, args) in programs.items()}

    # A scope is metadata: with every scope patched out, the same jaxprs
    # and, bit for bit, the same outputs.
    monkeypatch.setattr(layers, "scope",
                        lambda name: contextlib.nullcontext())
    plain = _programs(config)
    for name, (fn, args) in plain.items():
        assert str(jax.make_jaxpr(fn)(*args)) == jaxprs[name]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert not any(f"/{scope}/" in text for scope in layers.SCOPES)
    for ours, theirs in zip(scoped, _run(plain)):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_a_name_outside_the_list_is_refused():
    with pytest.raises(ValueError, match="nope"):
        layers.scope("nope")
    assert len(layers.SCOPES) == len(set(layers.SCOPES)) <= 12
