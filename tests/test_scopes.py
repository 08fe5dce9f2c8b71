"""The model step's named scopes: which scope each op of a compiled program
falls in, and that the scopes change nothing but metadata."""

from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import scopes
from repro.models import transformer as tx

B, PL, G = 2, 8, 4


def _compiled_decode(cfg):
    """``decode_step`` of ``cfg`` compiled on the CPU from shapes alone."""
    params = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tx.init_cache(cfg, B, PL + G + 1))
    one = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    def serve_decode(p, c, t, pos):
        return tx.decode_step(cfg, p, c, t, pos)

    return jax.jit(serve_decode).lower(params, cache, one, one).compile().as_text()


def _instructions(text: str):
    """``(name, opcode, op_name or None)`` of each instruction."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ ([\w\-]+)\(", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            yield m.group(1), m.group(2), op.group(1) if op else None


@pytest.fixture(scope="module")
def dense_decode():
    return _compiled_decode(get_smoke_config("qwen2.5-3b"))


def test_every_dot_is_in_a_layer_scope(dense_decode):
    ops = scopes.op_scopes(dense_decode)
    dots = [name for name, opcode, _ in _instructions(dense_decode) if opcode == "dot"]
    assert dots
    assert {ops[d] for d in dots} == {"attn", "mlp", "logits"}


def test_the_scans_slices_are_loop(dense_decode):
    ops = scopes.op_scopes(dense_decode)
    slices = [name for name, _, op in _instructions(dense_decode)
              if op and re.search(r"while/body/dynamic_(update_)?slice$", op)]
    assert slices
    assert {ops[s] for s in slices} == {"loop"}
    # the cache write inside the layer is the layer's own
    kv = [name for name, _, op in _instructions(dense_decode) if op and "/attn/kv_write/" in op]
    assert kv and {ops[k] for k in kv} == {"attn/kv_write"}
    assert "embed" in ops.values()


@pytest.mark.parametrize("arch,expected", [
    ("qwen2.5-3b", {"embed", "attn", "mlp", "logits", "loop"}),
    ("deepseek-v2-lite-16b", {"embed", "attn", "mlp", "moe", "logits", "loop"}),
    ("mamba2-130m", {"embed", "ssm", "logits", "loop"}),
    ("hymba-1.5b", {"embed", "attn", "ssm", "mlp", "logits", "loop"}),
])
def test_each_family_has_its_scopes(arch, expected):
    found = set(scopes.op_scopes(_compiled_decode(get_smoke_config(arch))).values())
    assert expected <= {s.split("/")[0] for s in found}
    parts = {f"{s}/{p}" for s, ps in scopes.PARTS.items() for p in ps}
    assert found <= set(scopes.SCOPES) | parts | {scopes.LOOP, scopes.OTHER}


def _compiled_prefill(cfg):
    """``prefill`` of ``cfg`` compiled on the CPU from shapes alone."""
    params = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tx.init_cache(cfg, B, PL + G + 1))
    toks = jax.ShapeDtypeStruct((B, PL), jnp.int32)

    def serve_prefill(p, t, c):
        return tx.prefill(cfg, p, t, c)

    return jax.jit(serve_prefill).lower(params, toks, cache).compile().as_text()


MOE_PARTS = {"moe/router", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared"}


@pytest.mark.parametrize("call,expected", [
    ("prefill", MOE_PARTS | {"attn/kv_write"}),
    ("decode", MOE_PARTS | {"attn/kv_write", "attn/absorbed"}),
])
def test_latent_and_expert_parts(call, expected):
    """The served calls of the latent-attention, routed-expert model map
    their expert and latent ops to the parts of ``moe`` and ``attn``: the
    routed experts' products to ``moe/experts``, the absorbed core's to
    ``attn/absorbed``."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    text = (_compiled_prefill if call == "prefill" else _compiled_decode)(cfg)
    parts = scopes.op_scopes(text)
    assert expected <= set(parts.values())
    dots = {parts[name] for name, opcode, _ in _instructions(text) if opcode == "dot"}
    assert "moe/experts" in dots and "moe/shared" in dots and "moe/router" in dots
    if call == "decode":
        assert "attn/absorbed" in dots


def _strip_metadata(text: str) -> str:
    head, rest = text.split("\nFileNames\n", 1)      # stack-frame tables
    text = head + rest[rest.index("\n\n%"):]
    return re.sub(r",?\s*metadata=\{[^}]*\}", "", text)


def test_scopes_change_only_metadata(dense_decode, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_decode(get_smoke_config("qwen2.5-3b"))
    assert "/attn/" not in bare and "/attn/" in dense_decode
    assert _strip_metadata(bare) == _strip_metadata(dense_decode)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/closed_call/attn/kv_write/scatter", "attn/kv_write"),
    ("jit(f)/while/body/checkpoint/mlp/dot_general", "mlp"),
    ("jit(f)/transpose(jvp(moe))/dot_general", "moe"),
    ("jit(f)/while/body/ssm/attn/add", "attn"),
    ("jit(f)/logits/dot_general", "logits"),
    ("jit(f)/embed/gather", "embed"),
    ("jit(f)/while/body/dynamic_slice", "loop"),
    ("jit(f)/while/cond/lt", "loop"),
    ("jit(f)/while/body/attention_mask/and", "loop"),
    ("jit(f)/mul", "other"),
    ("reduce_sum", "other"),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


@pytest.mark.parametrize("op_name,part", [
    ("jit(f)/while/body/attn/kv_write/scatter", "attn/kv_write"),
    ("jit(f)/while/body/attn/absorbed/dot_general", "attn/absorbed"),
    ("jit(f)/while/body/moe/while/body/experts/dot_general", "moe/experts"),
    ("jit(f)/while/body/moe/while/body/combine/scatter-add", "moe/combine"),
    ("jit(f)/while/body/moe/router/top_k", "moe/router"),
    ("jit(f)/while/body/moe/add", "moe"),
    ("jit(f)/while/body/mlp/experts/dot_general", "mlp"),
    ("jit(f)/while/body/dynamic_slice", "loop"),
])
def test_scope_of_parts(op_name, part):
    assert scopes.scope_of(op_name) == part


def test_op_without_metadata_takes_its_fused_scope_or_is_other():
    text = (
        "%fused_computation.3 (param_0: f32[2]) -> f32[2] {\n"
        "  %param_0 = f32[2]{0} parameter(0)\n"
        '  ROOT %add.2 = f32[2]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/attn/add"}\n'
        "}\n"
        "ENTRY %main.9 (p: f32[2]) -> f32[2] {\n"
        "  %copy.3 = f32[2]{0} copy(f32[2]{0} %p)\n"
        "  %fusion.4 = f32[2]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.3\n"
        '  ROOT %dot.1 = f32[2]{0} dot(%a, %b), metadata={op_name="jit(f)/mlp/dot_general"}\n'
        "}\n"
    )
    assert scopes.op_scopes(text) == {"param_0": "other", "add.2": "attn", "copy.3": "other",
                                      "fusion.4": "attn", "dot.1": "mlp"}


def test_a_program_compiled_without_scopes_is_refused(monkeypatch):
    """An executable from a compile-cache entry of an unscoped build has
    products but no scopes: the map refuses it instead of calling it all
    ``loop``."""
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_decode(get_smoke_config("qwen2.5-3b"))
    with pytest.raises(ValueError, match="no named scope"):
        scopes.op_scopes(bare)
