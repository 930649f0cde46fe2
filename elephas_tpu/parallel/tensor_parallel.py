"""Tensor parallelism (GSPMD sharding rules over the ``'model'`` axis).

The third mesh axis (``'model'``) the mesh has reserved since r1, made
real the idiomatic XLA way: no hand-written collectives — parameters
get ``NamedSharding`` annotations (Megatron-style: attention heads and
MLP hidden column-sharded, their output projections row-sharded, vocab
embedding/head vocab-sharded), inputs get the data sharding, and GSPMD
propagates the layout and inserts the all-reduces itself ("pick a mesh,
annotate shardings, let XLA insert collectives" — the scaling-book
recipe the rebuild is designed around). Composes with data parallelism
on the same mesh: ``build_mesh(num_data=D, num_model=M)``.

Sharding rules are (regex over the '/'-joined param path, PartitionSpec)
pairs: the bundled ``LM_RULES`` cover the flagship ``TransformerLM``;
any other model (flax or Keras-bridged) supplies its own table via
``rules=`` — ``param_specs`` FAILS LOUDLY when no rule shards anything,
so a model passed through the TP builders can never silently degrade to
replication. For Keras models, ``keras_param_rules`` translates rules
over Keras variable paths (``dense/kernel``) into rules over the
bridge's ``v{i}`` packing (serialize/keras_bridge.py).

Scope note: the reference has NO model parallelism of any kind
(SURVEY.md §2.2 — data-parallel only); this module is a beyond-parity
capability like the sequence-parallel layouts, aimed at models whose
parameters outgrow one chip. Sequence parallelism (ring/ulysses) covers
the long-SEQUENCE regime; this covers the wide-MODEL regime; the two
COMPOSE on one mesh via ``seq_parallel.make_lm_train_step`` (shard_map
manual over 'data'/'seq', 'model' left to GSPMD via ``axis_names``).
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu.engine.state import TrainState
from elephas_tpu.engine.step import init_train_state, make_train_step
from elephas_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

Rules = Sequence[Tuple[str, P]]

# Path-pattern -> PartitionSpec for TransformerLM parameters (paths are
# '/'-joined flax dict keys; kernels listed with their array layouts).
LM_RULES: Rules = (
    # qkv DenseGeneral: kernel (d_model, 3, heads, head_dim) — shard heads.
    (r".*/qkv/kernel$", P(None, None, MODEL_AXIS, None)),
    (r".*/qkv/bias$", P(None, MODEL_AXIS, None)),
    # attention output projection: kernel (d_model, d_model) — row-parallel
    # (contracting dim sharded; GSPMD inserts the psum).
    (r".*/out/kernel$", P(MODEL_AXIS, None)),
    (r".*/out/bias$", P()),
    # MLP: first Dense column-parallel, second row-parallel.
    (r".*/Dense_0/kernel$", P(None, MODEL_AXIS)),
    (r".*/Dense_0/bias$", P(MODEL_AXIS)),
    (r".*/Dense_1/kernel$", P(MODEL_AXIS, None)),
    (r".*/Dense_1/bias$", P()),
    # Vocabulary-sharded embedding and LM head.
    (r".*tok_embed/embedding$", P(MODEL_AXIS, None)),
    (r".*lm_head/kernel$", P(None, MODEL_AXIS)),
    (r".*lm_head/bias$", P(MODEL_AXIS)),
)

_LM_RULES = LM_RULES  # back-compat alias


def _spec_for(path: str, rules: Rules) -> P:
    for pattern, spec in rules:
        if re.match(pattern, path):
            return spec
    return P()  # LayerNorms, pos_embed, scalars: replicated


def param_specs(
    params, rules: Optional[Rules] = None, *, allow_replicated: bool = False
) -> Dict:
    """PartitionSpec pytree for ``params`` from (pattern, spec) rules.

    ``rules`` defaults to the bundled ``LM_RULES`` (the flagship
    ``TransformerLM``). Paths are '/'-joined pytree keys; unmatched
    leaves replicate (LayerNorms, scalars). If NO rule shards ANY
    parameter the whole model would silently train replicated —
    tensor parallelism as a no-op — so that raises unless the caller
    explicitly opts in with ``allow_replicated=True``.
    """
    if rules is None:
        rules = LM_RULES
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def path_str(kp):
        return "/".join(str(getattr(k, "key", k)) for k in kp)

    specs = {path_str(kp): _spec_for(path_str(kp), rules) for kp, _ in flat}
    if not allow_replicated and all(s == P() for s in specs.values()):
        sample = sorted(specs)[:8]
        raise ValueError(
            "tensor-parallel rules shard NO parameter of this model — "
            "training would silently run fully replicated. Pass rules="
            "[(path_regex, PartitionSpec), ...] matching your parameter "
            f"paths (e.g. {sample}), keras_param_rules(model, ...) for a "
            "Keras-bridged model, or allow_replicated=True to opt in to "
            "replication."
        )
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(
        treedef, [specs[path_str(kp)] for kp, _ in flat]
    )


def lm_param_specs(params, rules: Optional[Rules] = None) -> Dict:
    """PartitionSpec pytree for a ``TransformerLM`` parameter tree."""
    return param_specs(params, rules)


def decode_cache_specs(cache, axis: str = MODEL_AXIS) -> Dict:
    """PartitionSpec pytree for a ``TransformerLM`` decode cache (the
    serving KV pool, or a batch-1 prefill cache).

    K/V leaves — ``cached_key``/``cached_value``, laid out
    ``(batch|slots, heads, len, head_dim)`` — shard over their HEADS
    axis, matching the qkv kernel's head sharding in ``LM_RULES`` so the
    decode attention runs fully local per device and GSPMD only inserts
    the output projection's psum. Index leaves (``cache_index`` /
    ``pos_index``, scalar or per-slot vectors) replicate: every device
    advances every slot's write position identically.
    """

    from elephas_tpu.models.decode_cache import KV, leaf_kind

    def spec(path, leaf):
        if leaf_kind(path) == KV:
            assert leaf.ndim == 4, f"{path}: expected rank-4, got {leaf.shape}"
            return P(None, axis, None, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, cache)


def keras_param_rules(keras_model, rules: Rules) -> Rules:
    """Translate rules over Keras variable paths into bridge-key rules.

    The Keras bridge packs trainable variables as ``v0..vN``
    (serialize/keras_bridge.py), which hides layer names from the
    path-regex matcher. Keras-3 variables carry their own ``.path``
    (e.g. ``'sequential/dense_1/kernel'``); this matches ``rules``
    against those and returns an exact-key table usable with
    ``param_specs`` / the TP step builders.
    """
    out = []
    for i, var in enumerate(keras_model.trainable_variables):
        for pattern, spec in rules:
            if re.match(pattern, var.path):
                out.append((rf"^v{i}$", spec))
                break
    return tuple(out)


def _state_shardings(
    mesh: Mesh, state: TrainState, rules: Optional[Rules] = None
) -> TrainState:
    """NamedShardings for the full TrainState: params per the TP rules,
    optimizer slots following their parameter's layout, everything else
    replicated. ``state`` may be real arrays OR ``jax.eval_shape``
    ShapeDtypeStructs — only tree structure is inspected.

    Slots are matched STRUCTURALLY: any opt_state subtree whose pytree
    structure equals the param tree's (optax's mu/nu/trace mirrors) gets
    the param specs wholesale — matching by array shape would silently
    missharde slots whenever two different params share a shape (e.g.
    pos_embed vs a (d, d) projection)."""
    param_specs = lm_param_specs(state.params, rules)
    params_treedef = jax.tree_util.tree_structure(state.params)

    def is_param_tree(node):
        try:
            return jax.tree_util.tree_structure(node) == params_treedef
        except Exception:
            return False

    opt_specs = jax.tree_util.tree_map(
        lambda node: param_specs
        if is_param_tree(node)
        else jax.tree_util.tree_map(lambda _: P(), node),
        state.opt_state,
        is_leaf=is_param_tree,
    )
    spec_state = jax.tree_util.tree_map(lambda _: P(), state).replace(
        params=param_specs, opt_state=opt_specs
    )
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_state,
        is_leaf=lambda x: isinstance(x, P),
    )


def make_train_step_tp(compiled, mesh: Mesh, rules: Optional[Rules] = None):
    """Build ``step(state, x, y)`` jitted with dp×tp GSPMD shardings:
    batch over ``'data'``, parameters over ``'model'`` per ``rules``
    (default: the ``TransformerLM`` ``LM_RULES``; any model works with
    its own table — ``param_specs`` raises if nothing shards). Use
    ``init_state_tp`` for a state already placed on the mesh; x/y may be
    plain host arrays (jit shards them)."""
    from elephas_tpu.utils.compiler import tpu_compiler_options

    # Shapes only — never materialize a throwaway state (this module
    # exists for params that may not fit one host comfortably).
    abstract = jax.eval_shape(lambda: init_train_state(compiled))
    state_sh = _state_shardings(mesh, abstract, rules)
    data_sh = NamedSharding(mesh, P(DATA_AXIS, None))
    return jax.jit(
        make_train_step(compiled),
        in_shardings=(state_sh, data_sh, data_sh),
        out_shardings=(state_sh, NamedSharding(mesh, P())),
        compiler_options=tpu_compiler_options(),
    )


def init_state_tp(
    compiled, mesh: Mesh, rng=None, rules: Optional[Rules] = None
) -> TrainState:
    """TrainState with parameters/optimizer slots PLACED per the TP
    rules (the sharded-from-birth path a too-big-for-one-chip model
    needs; here init is tiny so a host init + device_put is fine)."""
    state = init_train_state(compiled, rng=rng)
    return jax.device_put(state, _state_shardings(mesh, state, rules))


# LM-named aliases (the flagship call sites).
make_lm_train_step_tp = make_train_step_tp
init_lm_state_tp = init_state_tp
