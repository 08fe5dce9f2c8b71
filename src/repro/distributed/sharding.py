"""Sharding rules: DP (+pod) x FSDP x TP x EP over the production mesh.

Rules map parameter tree paths to PartitionSpecs:

* TP (``model`` axis): attention heads, MLP hidden, experts, vocab.
* FSDP (``data`` axis): the complementary big dimension of each weight
  (ZeRO-3 -- optimizer moments inherit the same specs).
* DP (``pod`` axis): pure replication + gradient all-reduce by default;
  ``fsdp_pod=True`` folds the pod axis into FSDP (hillclimb option).
* EP: expert dims ride the ``model`` axis (see ``repro.models.moe``).

Dims that do not divide evenly by their axis size fall back to replication
(e.g. MQA's single KV head never shards over 16-way TP).
"""

from __future__ import annotations

import math
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def abstract_mesh(shape: tuple[int, ...], names: tuple[str, ...]):
    """A device-free mesh of ``shape`` with axis ``names`` (for rule tests)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(names))


def _axsize(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


class ShardingRules:
    def __init__(
        self,
        mesh: Mesh,
        *,
        fsdp_pod: bool = False,
        fsdp_params: bool = True,
    ):
        """``fsdp_params=False`` disables weight sharding over the data axis
        (TP-only + replication) -- the right choice for *serving*, where an
        FSDP layout would re-all-gather every weight on every decode step."""
        self.mesh = mesh
        names = mesh.axis_names
        self.has_pod = "pod" in names
        self.tp = "model"
        if self.has_pod and fsdp_pod:
            self.fsdp: Any = ("pod", "data")
            self.dp_axes: tuple[str, ...] = ("pod", "data")
        elif self.has_pod:
            self.fsdp = "data"
            self.dp_axes = ("pod", "data")
        else:
            self.fsdp = "data"
            self.dp_axes = ("data",)
        if not fsdp_params:
            self.fsdp = None

    # -- helpers ---------------------------------------------------------------

    def _fits(self, dim: int, axis) -> bool:
        n = _axsize(self.mesh, axis)
        return dim % n == 0 and dim >= n

    def _pick(self, shape: tuple[int, ...], prefs: list[tuple[int, Any]]) -> P:
        """Assign axes to dims in preference order, skipping non-dividing."""
        spec: list[Any] = [None] * len(shape)
        used: set[Any] = set()
        for dim_idx, axis in prefs:
            if axis is None or axis in used or dim_idx >= len(shape):
                continue
            if spec[dim_idx] is None and self._fits(shape[dim_idx], axis):
                spec[dim_idx] = axis
                used.add(axis)
        return P(*spec)

    # -- the rule table -------------------------------------------------------------

    def param_spec(self, path: str, shape: tuple[int, ...]) -> P:
        """path: '/'-joined key names, WITHOUT the stacked-layer leading dim."""
        tp, fsdp = self.tp, self.fsdp
        leaf = path.split("/")[-1]

        if leaf in ("embed", "unembed"):           # (V, d)
            return self._pick(shape, [(0, tp), (1, fsdp)])
        if leaf in ("enc_pos", "dec_pos"):         # (T, d)
            return self._pick(shape, [(0, fsdp)])
        if leaf == "w_q":                          # (d, H, hd) or MLA (d,H,qd)
            return self._pick(shape, [(1, tp), (0, fsdp)])
        if leaf in ("w_k", "w_v"):                 # (d, KV, hd)
            return self._pick(shape, [(1, tp), (0, fsdp)])
        if leaf == "w_o":                          # (H, hd, d)
            return self._pick(shape, [(0, tp), (2, fsdp)])
        if leaf in ("b_q", "b_k", "b_v"):          # (H, hd)
            return self._pick(shape, [(0, tp)])
        if leaf == "w_dkv":                        # (d, r+rope)
            return self._pick(shape, [(0, fsdp)])
        if leaf in ("w_uk", "w_uv"):               # (r, H, hd)
            return self._pick(shape, [(1, tp), (0, fsdp)])
        if "moe" in path or "shared" in path:
            if leaf == "router":                   # (d, E)
                return self._pick(shape, [(0, fsdp)])
            if len(shape) == 3:                    # experts (E, d, f)/(E, f, d)
                big = 1 if shape[1] >= shape[2] else 2
                other = 2 if big == 1 else 1
                return self._pick(shape, [(0, tp), (big, fsdp), (other, None)])
            if leaf in ("w_gate", "w_up"):         # shared (d, fs)
                return self._pick(shape, [(1, tp), (0, fsdp)])
            if leaf == "w_down":                   # shared (fs, d)
                return self._pick(shape, [(0, tp), (1, fsdp)])
        if leaf in ("w_gate", "w_up", "w_in"):     # (d, f)
            return self._pick(shape, [(1, tp), (0, fsdp)])
        if leaf in ("w_down", "w_out") and len(shape) == 2:
            # mlp (f, d) / mamba out (din, d): TP on contraction dim
            return self._pick(shape, [(0, tp), (1, fsdp)])
        if leaf == "b_in":                         # (f,)
            return self._pick(shape, [(0, tp)])
        if leaf == "conv_w":                       # (C, K)
            return self._pick(shape, [(0, fsdp)])
        # norms, biases, scalars, A/D/dt params: replicate
        return P(*([None] * len(shape)))

    # -- public API -------------------------------------------------------------------

    def state_shardings(self, state_shapes: Any) -> Any:
        """NamedShardings for a {params, opt} train-state shape pytree.

        Stacked layer groups have a leading layer dim -> rules shift by one.
        """

        def spec_for(path_tuple, leaf) -> NamedSharding:
            keys = [_key_str(k) for k in path_tuple]
            # strip opt-state prefixes so moments shard like their params
            while keys and keys[0] in ("params", "opt", "m", "v"):
                keys = keys[1:]
            path = "/".join(keys)
            shape = leaf.shape
            if len(shape) == 0:  # scalars (opt step counters etc.)
                return NamedSharding(self.mesh, P())
            if _is_stacked(keys, shape):
                inner = self.param_spec(path, shape[1:])
                return NamedSharding(self.mesh, P(None, *inner))
            return NamedSharding(self.mesh, self.param_spec(path, shape))

        paths_and_leaves = jax.tree_util.tree_flatten_with_path(state_shapes)[0]
        treedef = jax.tree.structure(state_shapes)
        specs = [spec_for(p, l) for p, l in paths_and_leaves]
        return jax.tree.unflatten(treedef, specs)

    def batch_sharding(self) -> Any:
        return NamedSharding(self.mesh, P(self.dp_axes))

    def batch_spec(self, ndim: int) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.dp_axes, *([None] * (ndim - 1))))

    def cache_shardings(self, cache_shapes: Any) -> Any:
        """KV/SSM caches: batch over DP axes, kv-heads over TP if they fit.

        Cache leaves are stacked (L, B, ...); batch is dim 1.
        """

        def spec_for(path_tuple, leaf) -> NamedSharding:
            keys = [_key_str(k) for k in path_tuple]
            shape = leaf.shape
            name = keys[-1]
            spec: list[Any] = [None] * len(shape)
            if len(shape) >= 2:
                # dim 0 is the stacked layer dim; batch is dim 1
                if self._fits(shape[1], self.dp_axes):
                    spec[1] = self.dp_axes
                if name in ("k", "v", "cross_k", "cross_v") and len(shape) == 5:
                    # (L,B,S,KV,hd): TP on KV heads when they divide the axis,
                    # else context-parallel (sequence) sharding of the cache.
                    if self._fits(shape[3], self.tp):
                        spec[3] = self.tp
                    elif self._fits(shape[2], self.tp):
                        spec[2] = self.tp
                if name in ("c", "k_rope") and len(shape) == 4:
                    # MLA latent cache (L,B,S,r): context-parallel on S
                    if self._fits(shape[2], self.tp):
                        spec[2] = self.tp
                if name == "state" and len(shape) == 5:
                    # (L,B,H,P,N): prefer the state dim N (a power of two,
                    # always TP-divisible) over heads H (often not, e.g.
                    # 24 heads vs 16-way TP -> padded-H resharding with a
                    # 214 MB/step all-gather; §Perf mamba2 decode iter 3)
                    if self._fits(shape[4], self.tp):
                        spec[4] = self.tp
                    elif self._fits(shape[2], self.tp):
                        spec[2] = self.tp
                # NOTE: the conv cache (L,B,K-1,C) is deliberately NOT
                # C-sharded over TP.  It is tiny (~66 MB replicated for
                # mamba2-130m) but C-sharding it propagates a padded
                # H-sharding into the SSM state update, which SPMD then
                # resolves with a 214 MB per-step state all-gather
                # (§Perf mamba2 decode iteration 2: 4.7 ms -> sub-ms bound).
            return NamedSharding(self.mesh, P(*spec))

        paths_and_leaves = jax.tree_util.tree_flatten_with_path(cache_shapes)[0]
        treedef = jax.tree.structure(cache_shapes)
        specs = [spec_for(p, l) for p, l in paths_and_leaves]
        return jax.tree.unflatten(treedef, specs)


def _key_str(k) -> str:
    return getattr(k, "key", getattr(k, "name", getattr(k, "idx", str(k))))


def _is_stacked(keys: list[str], shape: tuple[int, ...]) -> bool:
    """Layer-group params/caches carry a leading stacked-layer dim."""
    if not keys:
        return False
    head = keys[0]
    return head not in ("embedding", "final_norm", "enc_norm", "enc_pos", "dec_pos")


def _spec_first(p: P):
    return p[0] if len(p) else None
