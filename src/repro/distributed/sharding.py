"""Logical-axis sharding rules (DP/TP/EP/SP + SAGe blocks) for the meshes.

Model code annotates activations with *logical* names via :func:`shard_act`;
a context-installed :class:`Rules` maps them to mesh PartitionSpecs. With no
rules installed (unit tests, single device), annotations are no-ops.

Parameter shardings are derived from the param-tree *path* by pattern
(:func:`param_spec`), so every architecture gets Megatron-style TP + EP
without per-model boilerplate.

The SAGe store shards over *blocks* — the paper's independent unit of
storage, decode, and checkpointing (its per-NAND-channel partitions, §5.3):
:func:`make_block_mesh` builds the 1-D store-level mesh and
:func:`block_sharding` / :func:`block_specs` place the leading block axis of
every prepared stream array on it. ``Rules`` carries the same axis name
(``block_axis``) so model-side code can annotate SAGe-derived activations
with the ``sage_blocks`` logical name.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

BLOCK_AXIS = "blocks"  # the store-level mesh axis (SAGe block partitions)


def make_block_mesh(shards: Optional[int] = None, *, axis: str = BLOCK_AXIS) -> Mesh:
    """1-D store-level mesh over the first ``shards`` local devices.

    ``shards=None`` uses every visible device. On a CPU-only container the
    device pool can be widened with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    initializes) — the recipe the shard benchmark and CI smoke use."""
    devs = jax.devices()
    n = len(devs) if shards is None else int(shards)
    if not (1 <= n <= len(devs)):
        raise ValueError(
            f"cannot build a {n}-shard block mesh with {len(devs)} visible "
            f"device(s); on CPU, widen the pool with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={max(n, 2)}"
        )
    return Mesh(np.asarray(devs[:n]), (axis,))


def block_axis_name(mesh: Mesh) -> str:
    """The block axis of a store-level mesh (its single/leading axis)."""
    return mesh.axis_names[0]


def block_shard_count(mesh: Optional[Mesh]) -> int:
    """Number of block shards a mesh implies (1 for ``None``)."""
    if mesh is None:
        return 1
    return int(mesh.devices.shape[0])


def block_spec(ndim: int, *, axis: str = BLOCK_AXIS) -> P:
    """PartitionSpec sharding dim 0 (the block axis) of an ndim array."""
    return P(axis, *([None] * (ndim - 1)))


def block_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """NamedSharding placing an array's leading block dim on ``mesh``."""
    return NamedSharding(mesh, block_spec(ndim, axis=block_axis_name(mesh)))


def block_specs(tree, mesh: Mesh):
    """Per-leaf block-axis NamedShardings for a pytree of block-major arrays."""
    return jax.tree.map(lambda v: block_sharding(mesh, v.ndim), tree)


@dataclasses.dataclass(frozen=True)
class Rules:
    """Maps logical activation axes -> PartitionSpec for the active mesh."""

    mesh: Mesh
    data_axes: tuple[str, ...] = ("data",)  # pure DP axes ("pod","data") multi-pod
    model_axis: str = "model"
    seq_shard: bool = False  # SP: shard activation seq dim over model axis
    pure_dp: bool = False  # fold the model axis into DP (small models)
    block_axis: str = BLOCK_AXIS  # SAGe store: leading block dim of reads

    def batch(self):  # batch dim of activations / inputs
        axes = tuple(a for a in self.data_axes if a in self.mesh.axis_names)
        if self.pure_dp and self.model_axis in self.mesh.axis_names:
            axes = axes + (self.model_axis,)
        return axes or None

    def spec(self, name: str) -> P:
        b = self.batch()
        m = None if self.pure_dp else self.model_axis
        s = m if (self.seq_shard and not self.pure_dp) else None
        table = {
            "act_btd": P(b, s, None),  # (B, S, D) between blocks
            "act_heads": P(b, None, m),  # (B, S, H*Dh) after attention
            "act_ff": P(b, None, m),  # (B, S, FF) inside MLP
            "act_btv": P(b, None, m),  # logits (B, S, V)
            "tokens": P(b, None),
            "kv_cache": P(b, None, m, None),  # (B, T, KV, Dh)
            "kv_cache_seq": P(b, m, None, None),  # long-context: shard T
            "ssm_state": P(b, m, None, None),  # (B, H, P, N)
            # SAGe store outputs: block-major decode/format arrays (B, ...)
            "sage_blocks": P(self.block_axis if self.block_axis in self.mesh.axis_names else None),
        }
        return table[name]

    def sharding(self, name: str) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(name))


def install_rules(rules: Optional[Rules]) -> None:
    _state.rules = rules


def current_rules() -> Optional[Rules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Rules):
    prev = current_rules()
    install_rules(rules)
    try:
        yield rules
    finally:
        install_rules(prev)


def shard_act(x: jax.Array, name: str) -> jax.Array:
    """Annotate an activation with a logical sharding (no-op without rules)."""
    r = current_rules()
    if r is None:
        return x
    try:
        spec = r.spec(name)
    except KeyError:
        return x
    if len(spec) > x.ndim:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(r.mesh, spec))


# --------------------------------------------------------------------------
# parameter sharding by path pattern
# --------------------------------------------------------------------------

# (pattern, spec builder) — first match wins; ndim-adjusted with leading Nones
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed", ("model", None)),  # (V, D) vocab-sharded
    (r"lm_head", (None, "model")),  # (D, V)
    (r"\bwq\b|\bwk\b|\bwv\b", (None, "model")),
    (r"\bbq\b|\bbk\b|\bbv\b", ("model",)),
    (r"\bwo\b", ("model", None)),
    (r"experts.*(up|gate)", ("model", None, None)),  # (E, D, F) EP
    (r"experts.*down", ("model", None, None)),  # (E, F, D) EP
    (r"(shared|mlp|enc_mlp|dec_mlp).*(up|gate)", (None, "model")),
    (r"(shared|mlp|enc_mlp|dec_mlp).*down", ("model", None)),
    (r"router", (None, None)),
    (r"in_(z|x)", (None, "model")),  # mamba d_inner projections
    (r"out_proj", ("model", None)),
    (r"conv_x|ssm_(a|d|dtb)|dt_w", ("model",)),  # per-head / d_inner params
    (r"pos_emb", (None, None)),
    (r".*", ()),  # default: replicate
]


def param_spec(path: str, ndim: int, rules: Rules) -> P:
    if rules.pure_dp:
        return P()
    for pat, axes in _PARAM_RULES:
        if re.search(pat, path):
            ax = list(axes)
            break
    else:  # pragma: no cover
        ax = []
    # pad leading None for stacked-layer axes
    ax = [None] * (ndim - len(ax)) + [
        (rules.model_axis if a == "model" else a) for a in ax
    ]
    ax = ax[:ndim]
    # divisibility fixups (replicating any dim the mesh can't divide) are
    # the caller's job — see param_shardings
    return P(*ax)


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
    return "/".join(out)


def param_shardings(params_tree, rules: Rules, shapes=None):
    """NamedShardings for a param pytree (by path pattern), with divisibility
    fixups: any dim not divisible by its assigned axis is replicated."""
    if rules.pure_dp:
        rep = NamedSharding(rules.mesh, P())
        return jax.tree.map(lambda _: rep, params_tree)
    msize = rules.mesh.devices.shape[list(rules.mesh.axis_names).index(rules.model_axis)]

    def one(path, leaf):
        shape = leaf.shape
        spec = param_spec(_path_str(path), len(shape), rules)
        fixed = []
        for dim, ax in zip(shape, spec):
            if ax == rules.model_axis and dim % msize != 0:
                fixed.append(None)
            else:
                fixed.append(ax)
        return NamedSharding(rules.mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(one, params_tree)
