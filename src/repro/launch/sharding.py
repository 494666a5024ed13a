"""Path-based sharding rules: model-parallel axis per parameter, FSDP axis
over the worker (data / pod×data) axes, KV-cache and activation specs.

The same deterministic rule feeds (a) the jit ``in_shardings`` and (b) the
Mode-B robust-gather hook, so the custom VJP always all-gathers exactly the
axis the spec sharded.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.sharded import fsdp_axis_for
from repro.models import init_params

# --------------------------------------------------------- model-axis rule

# leaf name -> preferred model-sharded dim (checked for divisibility)
_MODEL_AXIS = {
    "wq": 1, "wk": 1, "wv": 1, "wo": 0,
    "bq": 0, "bk": 0, "bv": 0,
    "w1": 1, "w3": 1, "w2": 0,
    "we1": 2, "we3": 2, "we2": 1,
    "in_proj": 1, "out_proj": 0, "x_proj": 0, "dt_proj": 1,
    "conv_w": 1, "conv_b": 0, "A_log": 0, "D": 0, "dt_bias": 0,
    "wg": 1, "wr": 1,
    "embed": 0, "unembed": 1, "dec_pos": 1,
}


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for e in path:
        if hasattr(e, "key"):
            out.append(str(e.key))
        elif hasattr(e, "name"):
            out.append(str(e.name))
    return tuple(out)


def model_axis_rule(path_names: Tuple[str, ...], shape, model_size: int) -> Optional[int]:
    name = path_names[-1] if path_names else ""
    ax = _MODEL_AXIS.get(name)
    if name == "wv" and "mlp" in path_names:  # rwkv channel-mix wv: (F, D)
        ax = 0
    if name in ("we1", "we2", "we3") and shape and shape[0] % model_size == 0:
        ax = 0  # expert parallelism when E divides the model axis (§Perf it.2)
    if ax is None or ax >= len(shape):
        return None
    if shape[ax] % model_size != 0:
        return None
    if functools.reduce(lambda a, b: a * b, shape, 1) < (1 << 14):
        return None
    return ax


# --------------------------------------------------------- parameter plans


def abstract_params(cfg: ModelConfig, dtype=jnp.bfloat16):
    # the key is abstract too: nothing runs, not even the key's creation
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(functools.partial(init_params, cfg, dtype=dtype), key)


def plan_params(cfg: ModelConfig, mesh: Mesh, *, fsdp: bool, dtype=jnp.bfloat16):
    """Returns (specs, plans):
      specs — PartitionSpec tree matching the full (stacked) param tree;
      plans — {'top': int-tree, 'blocks': int-tree over a group slice},
              leaf = FSDP gather axis in the local view, -1 = replicated;
              plus plans['model'] = {'top': ..., 'blocks': ...}, the same
              trees holding each leaf's 'model' axis, -1 = none.
    """
    shapes = abstract_params(cfg, dtype)
    model_size = mesh.shape["model"]
    waxes = tuple(a for a in mesh.axis_names if a != "model")
    m = 1
    for a in waxes:
        m *= mesh.shape[a]

    def entry(path, leaf, stacked: bool):
        names = _path_names(path)
        shape = leaf.shape[1:] if stacked else leaf.shape
        ma = model_axis_rule(names, shape, model_size)
        fa = fsdp_axis_for(shape, m, ma) if fsdp else None
        spec = [None] * len(shape)
        if ma is not None:
            spec[ma] = "model"
        if fa is not None:
            spec[fa] = waxes if len(waxes) > 1 else waxes[0]
        if stacked:
            spec = [None] + spec
        return (P(*spec), -1 if fa is None else fa,
                -1 if ma is None else ma)

    def plan(tree, stacked: bool):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        cols = zip(*[entry(path, leaf, stacked) for path, leaf in flat])
        return [jax.tree_util.tree_unflatten(treedef, list(c)) for c in cols]

    top_specs, top_plan, top_model = plan(
        {k: v for k, v in shapes.items() if k != "blocks"}, stacked=False)
    blk_specs, blk_plan, blk_model = plan(shapes["blocks"], stacked=True)
    specs = {**top_specs, "blocks": blk_specs}
    plans = {"top": top_plan, "blocks": blk_plan,
             "model": {"top": top_model, "blocks": blk_model}}
    return specs, plans


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def strip_model(spec_tree):
    """Drop the 'model' entries of a spec tree — shard_map regions manual
    over the worker axes only may not mention the auto 'model' axis in their
    in/out_specs (the Mode-B partial-manual lowering, DESIGN.md §3)."""
    def strip(s):
        return P(*[None if e == "model" else e for e in s])
    return jax.tree.map(strip, spec_tree, is_leaf=lambda x: isinstance(x, P))


def opt_specs(opt_state_shapes, param_specs):
    """Optimizer-state specs: mirror the param specs for param-shaped state
    (momentum/adam), replicate scalars, empty for stateless SGD."""
    state = opt_state_shapes
    if isinstance(state, tuple) and not state:  # sgd
        return ()
    if isinstance(state, dict) and set(state) == {"m", "v", "t"}:  # adam
        return {"m": param_specs, "v": param_specs, "t": P()}
    pstruct = jax.tree_util.tree_structure(param_specs,
                                           is_leaf=lambda x: isinstance(x, P))
    try:
        if jax.tree_util.tree_structure(state) == pstruct:  # momentum
            return param_specs
    except Exception:
        pass
    return jax.tree.map(lambda _: P(), state)  # adagrad-norm scalar etc.


def sds(shape, dtype, mesh: Mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def sds_tree(shapes, specs, mesh: Mesh):
    """ShapeDtypeStructs with NamedShardings for an abstract tree + specs."""
    flat_sh, treedef = jax.tree_util.tree_flatten(shapes)
    flat_sp = treedef.flatten_up_to(specs)
    return jax.tree_util.tree_unflatten(
        treedef, [sds(a.shape, a.dtype, mesh, s)
                  for a, s in zip(flat_sh, flat_sp)])


def batch_sds(cfg: ModelConfig, mesh: Mesh, global_batch: int, seq_len: int,
              *, kind: str = "train", dtype=jnp.bfloat16):
    """(specs, example) for the input batch — the ONE builder both Mode-B
    step builders draw their batch specs and example ShapeDtypeStructs from,
    so the family-dependent ``extra`` leaves (audio frames / vlm patches)
    cannot drift between them again (the PR-7 bug: ``build_mlmc_train_step``
    dropped them and could not run the whisper/vision configs)."""
    spec = batch_specs(cfg, mesh, global_batch, kind)
    B = global_batch
    ex = {"tokens": sds((B, seq_len), jnp.int32, mesh, spec["tokens"])}
    if kind == "train":
        ex["labels"] = sds((B, seq_len), jnp.int32, mesh, spec["labels"])
    if "extra" in spec:
        extra = {}
        if cfg.family == "audio":
            extra["frames"] = sds((B, cfg.encoder_seq, cfg.d_model), dtype,
                                  mesh, spec["extra"]["frames"])
        if cfg.family == "vlm":
            extra["patches"] = sds((B, cfg.n_image_tokens, cfg.d_model), dtype,
                                   mesh, spec["extra"]["patches"])
        ex["extra"] = extra
    return spec, ex


# --------------------------------------------------------- data & cache


def batch_specs(cfg: ModelConfig, mesh: Mesh, global_batch: int, kind: str):
    """Specs for the input batch pytree."""
    waxes = tuple(a for a in mesh.axis_names if a != "model")
    m = 1
    for a in waxes:
        m *= mesh.shape[a]
    b_ax = (waxes if len(waxes) > 1 else waxes[0]) if global_batch % m == 0 else None
    tok = P(b_ax, None) if kind != "decode" else P(b_ax)
    spec = {"tokens": tok, "labels": P(b_ax, None)}
    extra = {}
    if cfg.family == "audio":
        extra["frames"] = P(b_ax, None, None)
    if cfg.family == "vlm":
        extra["patches"] = P(b_ax, None, None)
    if kind == "train":
        if extra:
            spec["extra"] = extra
        return spec
    if kind == "prefill":
        return {"tokens": tok, **({"extra": extra} if extra else {})}
    return {"tokens": tok}


def cache_specs(cfg: ModelConfig, mesh: Mesh, global_batch: int):
    """Specs for the decode cache (leaves stacked over n_groups)."""
    model_size = mesh.shape["model"]
    data_ok = global_batch % mesh.shape["data"] == 0

    def leaf_spec(path, leaf):
        names = _path_names(path)
        shape = leaf.shape  # (n_groups, B, ...)
        name = names[-1]
        spec = [None] * len(shape)
        if data_ok and shape[1] % mesh.shape["data"] == 0:
            spec[1] = "data"
        if name in ("k", "v"):  # (g, B, S, KV, hd)
            if shape[3] % model_size == 0:
                spec[3] = "model"
            elif shape[2] % model_size == 0:
                spec[2] = "model"
        elif name == "conv":  # (g, B, k-1, di)
            if shape[3] % model_size == 0:
                spec[3] = "model"
        elif name == "ssm":  # (g, B, di, ds)
            if shape[2] % model_size == 0:
                spec[2] = "model"
        elif name == "state":  # (g, B, H, hd, hd)
            if shape[2] % model_size == 0:
                spec[2] = "model"
        elif name == "prev":  # (g, B, D)
            if shape[2] % model_size == 0:
                spec[2] = "model"
        return P(*spec)

    from repro.models import init_cache

    shapes = jax.eval_shape(lambda: init_cache(cfg, global_batch, 1))
    # note: caller re-evaluates with the true seq_len; here only structure is
    # needed, so build specs from the real abstract tree instead:
    return shapes, leaf_spec


def cache_spec_tree(cfg: ModelConfig, mesh: Mesh, batch: int, seq_len: int):
    from repro.models import init_cache

    shapes = jax.eval_shape(functools.partial(init_cache, cfg, batch, seq_len))
    _, leaf_spec = cache_specs(cfg, mesh, batch)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [leaf_spec(path, leaf) for path, leaf in flat]
    return shapes, jax.tree_util.tree_unflatten(treedef, specs)
