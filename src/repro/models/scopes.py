"""The model step's named scopes, and which scope each op of a compiled
program belongs to.

The models mark their parts with ``jax.named_scope``, which is compile-time
metadata only: it changes each op's ``op_name`` and nothing else.

- ``embed``: the embedding lookup.
- ``attn``: a layer's pre-norm, projections, rotary embedding, cache write,
  attention core, output projection and residual add; the cache write is
  also under ``attn/kv_write``, and a prefill's flash kernel (with its
  layout changes) under ``attn/flash``.  A hybrid layer's mix of its
  attention and SSM heads is ``attn`` too.
- ``mlp``, ``moe``: the second norm, the MLP or expert layer, the residual.
- ``ssm``: the Mamba mixer (with its norm and residual in an SSM stack).
- ``logits``: the final norm and the output head.

Parts of a scope (``PARTS``) are nested scopes inside it:

- ``attn/kv_write``: the cache write; ``attn/flash``: a GQA prefill's flash
  kernel; ``attn/absorbed``: latent attention's absorbed core against the
  latent cache (the query absorption, scores, softmax, values, and the lift
  to each head).
- ``moe/router``: the router's scores and top-k; ``moe/dispatch``: sorting
  the routed pairs into blocks and gathering each block's rows;
  ``moe/experts``: the routed experts' products; ``moe/combine``: the
  weighted sum back into the tokens; ``moe/shared``: the shared experts.

``op_scopes`` reads a compiled program's ``as_text()``: each instruction
maps to the innermost of those scopes in its ``op_name``, as ``scope/part``
where it lies in a part (its scope is what precedes the ``/``); a fusion that has
none takes the scope of the instructions it fuses.  An instruction with no
scope inside a while loop is the layer scan's own slicing and stacking of
weights and caches (``loop``); anything else is ``other``.  The instruction
names are those of the device ops in a profiler trace, so the map splits a
program's device time by scope.

JAX's persistent compilation cache leaves ``op_name`` out of its key, so a
program built with scopes can be handed the executable of an earlier build
without them; ``op_scopes`` raises on a program whose matrix products carry
no scope rather than count all of it as ``loop`` and ``other``.
"""

from __future__ import annotations

import re

SCOPES = ("embed", "attn", "mlp", "moe", "ssm", "logits")
PARTS = {"attn": ("kv_write", "flash", "absorbed"),
         "moe": ("router", "dispatch", "experts", "combine", "shared")}
LOOP, OTHER = "loop", "other"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# A scope is a whole component of the name stack, possibly wrapped by a
# transformation: ``.../attn/...``, ``jvp(mlp)``, ``transpose(jvp(attn))``.
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")
_LOOP = re.compile(r"(?:^|/)while/(?:body|cond)(?:/|$)")
_PRODUCT = re.compile(r"=\s*\S+\s+(?:dot|convolution)\(")


def scope_of(op_name: str) -> str:
    """The scope of one ``op_name`` from a compiled program's metadata:
    ``scope/part`` where the op lies in one of the scope's parts."""
    found = list(_SCOPE.finditer(op_name))
    if found:
        scope = found[-1].group(1)
        if scope in PARTS:
            inner = re.split(r"[/()]", op_name[found[-1].end():])
            named = [c for c in inner if c in PARTS[scope]]
            if named:
                return f"{scope}/{named[-1]}"
        return scope
    return LOOP if _LOOP.search(op_name) else OTHER


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope or scope/part}`` for every instruction of
    a compiled program's text (``jax.stages.Compiled.as_text()``)."""
    scopes: dict[str, str] = {}
    fused: dict[str, str] = {}  # unnamed instruction -> the computation it calls
    first: dict[str, str] = {}  # computation -> the scope of its first scoped instruction
    comp = ""
    for line in hlo_text.splitlines():
        if head := _COMPUTATION.match(line):
            comp = head.group(1)
        elif m := _INSTR.match(line):
            op_name = _OP_NAME.search(line)
            scope = scopes[m.group(1)] = scope_of(op_name.group(1)) if op_name else OTHER
            if scope != OTHER:
                first.setdefault(comp, scope)
            elif not op_name and (calls := _CALLS.search(line)):
                fused[m.group(1)] = calls.group(1)
    for name, callee in fused.items():
        scopes[name] = first.get(callee, OTHER)
    if _PRODUCT.search(hlo_text) and not set(SCOPES) & {
            s.split("/")[0] for s in scopes.values()}:
        raise ValueError(
            "the program's matrix products carry no named scope: it was built "
            "without them, or loaded from a compile-cache entry that was"
        )
    return scopes
