"""Weight-only int8 quantization for the decode path.

Every generated token re-reads the whole model, so int8 weights with
per-output-channel scales halve the per-step weight HBM traffic vs bf16
(4x vs f32) and halve the weight FOOTPRINT (a ~2x-larger model fits one
chip). The dequantize runs INSIDE the step program (int8 leaves the HBM;
verified in the compiled HLO — the weights stay s8, nothing is hoisted
out of the scan).

Round-5 builder-measured reality: with the DEQUANTIZE
path (dense bf16 rebuilt inside the step program before each matmul) the
throughput win stalled at +4-11% at batch 1, ~0 at batch 8-16 — per-op
overhead and the convert+scale absorbed most of the saved stream time.
The NATIVE path closes that gap: :func:`quantized_dot` contracts the
activations against the int8 values directly (Pallas kernel on TPU,
``lax.dot_general`` fallback elsewhere — ops/int8_matmul.py) and folds
the per-channel scale into the f32 accumulator AFTER the contraction, so
no dense ``W~`` exists even as a fused intermediate. ``KUBEML_INT8_MATMUL=1``
routes every quantized dense projection of the decode step through it
(models/layers.py ``QuantizableDense``); the dequantize path remains the
default and the fallback for modules the native path doesn't cover (MoE
expert stacks).

Scheme: symmetric per-output-channel int8 —

    scale[c] = max(|W[..., c]|) / 127        (last axis = output channel)
    Q = round(W / scale),  W~ = Q * scale    (bf16/f32 accumulation)

Only floating-point matrices with >= ``min_size`` elements quantize
(embeddings, attention/MLP kernels, lm_head); biases, LayerNorm scales,
and small vectors stay exact — they are a rounding error of the byte
traffic and disproportionately sensitive. Quantized leaves live in the
variables tree as :class:`QuantizedTensor` pytree nodes, so the SAME tree
flows through jit/device_put unchanged and ``dequantize_tree`` (traced
into the decode program) restores a dense tree for ``module.apply``.

The reference has no quantization (or serving runtime) to compare; this
extends the HBM-bound analysis the round-4 engine is built on
(VERDICT r4 next-2).
"""

from __future__ import annotations

import collections
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# don't quantize small leaves: no bandwidth to win, outsized quality cost
MIN_QUANT_SIZE = 4096


class QuantizedTensor(NamedTuple):
    """int8 values + per-output-channel f32 scales (a pytree node, so it
    travels through jit/device_put like any leaf pair)."""

    q: Any  # int8, same shape as the original weight
    s: Any  # f32, shape [..., 1 x (ndim-1), channels] broadcast over q

    @property
    def shape(self):
        return self.q.shape


def _quantize_leaf(w) -> QuantizedTensor:
    w = jnp.asarray(w)
    absmax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q=q, s=scale.astype(jnp.float32))


def _wants_quant(leaf) -> bool:
    return (hasattr(leaf, "dtype") and hasattr(leaf, "ndim")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and leaf.ndim >= 2
            and int(leaf.size) >= MIN_QUANT_SIZE)


def _gather_accessed(path) -> bool:
    """Embedding-family leaves (token_embed/pos_embed/...): decode GATHERS
    one row per token instead of streaming the table, so quantizing them
    saves no per-step bandwidth and only costs quality — they stay exact,
    and the byte accounting excludes them."""
    return any("embed" in str(getattr(k, "key", k)).lower() for k in path)


def quantize_tree(variables: dict) -> dict:
    """Quantize every eligible weight leaf of a variables pytree (host or
    device); returns the same structure with QuantizedTensor nodes.
    Embedding tables are left exact (gather-accessed — see
    ``_gather_accessed``)."""
    import flax.linen as nn

    unboxed = nn.meta.unbox(variables)

    def one(path, leaf):
        if not _gather_accessed(path) and _wants_quant(leaf):
            return _quantize_leaf(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(one, unboxed)


def _is_q(x) -> bool:
    return isinstance(x, QuantizedTensor)


def dequantize_tree(variables: dict, dtype=jnp.bfloat16) -> dict:
    """Densify a quantized tree — TRACE THIS INSIDE the step program so the
    HBM read is int8 and the convert+scale fuses into the consumer (outside
    jit it would just materialize bf16 copies and forfeit the win)."""

    def one(leaf):
        if _is_q(leaf):
            return (leaf.q.astype(dtype) * leaf.s.astype(dtype))
        return leaf

    return jax.tree.map(one, variables, is_leaf=_is_q)


def is_floating(leaf) -> bool:
    return jnp.issubdtype(getattr(leaf, "dtype", jnp.int32), jnp.floating)


# casts cast_leaves keeps in flight: a leaf's way to the device overlaps the
# casts before it, and the wide copies alive stay a few leaves, not a tree
_CASTS_AHEAD = 2


@jax.tree_util.register_pytree_node_class
class PaddedRows:
    """A 2-d table held with each row padded to whole lane rows: ``rows`` is
    ``[n, padded]`` and its first ``width`` columns are the table. A pytree
    node, as :class:`QuantizedTensor` is, so the same tree flows through
    jit and device_put; ``width`` is static, and :func:`unpadded`, traced
    INSIDE a program, hands ``module.apply`` the table at its own shape: on
    a TPU that slice is a bitcast (the padded array is how the device
    stores ``[n, width]`` by rows anyway) and a lookup gathers from the
    held array where it lies."""

    def __init__(self, rows, width: int):
        self.rows, self.width = rows, int(width)

    def tree_flatten(self):
        return (self.rows,), self.width

    @classmethod
    def tree_unflatten(cls, width, children):
        return cls(children[0], width)


def _is_padded(x) -> bool:
    return isinstance(x, PaddedRows)


def unpadded(variables: dict) -> dict:
    """The tree with every :class:`PaddedRows` node the table it holds."""
    return jax.tree.map(
        lambda l: l.rows[:, :l.width] if _is_padded(l) else l,
        variables, is_leaf=_is_padded)


def padded_bytes(variables: dict) -> int:
    """The bytes the tree's :class:`PaddedRows` nodes hold beyond their
    tables."""
    return sum(int(l.rows.nbytes) * (l.rows.shape[1] - l.width)
               // l.rows.shape[1]
               for l in jax.tree.leaves(variables, is_leaf=_is_padded)
               if _is_padded(l))


def held_width(shape, layout) -> int:
    """The width to hold a 2-d table of ``shape`` at, on a device whose own
    layout for that shape is ``layout``, so that a row lies contiguous on
    the lanes: its own where the device stores it by rows already (or says
    nothing of tiles); else the next whole number of the layout's lane
    tiles, at which the device's choice is rows. A TPU stores
    ``f32[50257, 1600]`` as ``{0,1:T(8,128)}``, the VOCABULARY on the lanes
    (50,257 pads to 50,304, less than 1,600 to 1,664), and a program that
    gathers rows of such an operand first copies all of it."""
    width = int(shape[1])
    if (layout is None or not layout.tiling
            or tuple(layout.major_to_minor) == (0, 1)):
        return width
    lanes = int(layout.tiling[0][-1])
    return -(-width // lanes) * lanes


def rows_on_lanes(leaf):
    """``leaf``, a 2-d table, on the device with a row contiguous on the
    lanes: placed, and nothing else, where the device's own layout for it
    is by rows; else padded on the device to :func:`held_width` as a
    :class:`PaddedRows` (and left as placed should the device store even
    that by columns)."""
    placed = jnp.asarray(leaf)
    to = held_width(placed.shape, placed.format.layout)
    if to == placed.shape[1]:
        return placed
    rows = jnp.pad(placed, ((0, 0), (0, to - placed.shape[1])))
    if tuple(rows.format.layout.major_to_minor) != (0, 1):
        return placed
    return PaddedRows(rows, placed.shape[1])


def cast_leaves(variables: dict, types: list, rows=None) -> dict:
    """The tree with leaf ``i`` (in tree order, a QuantizedTensor one leaf)
    held in ``types[i]`` on the device, or as it is where that is None:
    each leaf is put on the device and cast there by itself, and its wide
    copy dropped before the third one after it arrives, so the peak is the
    narrow tree plus a few wide leaves. A leaf with ``rows[i]`` true is
    then held as :func:`rows_on_lanes` holds a table."""
    leaves, treedef = jax.tree.flatten(variables, is_leaf=_is_q)
    out, ahead = [], collections.deque()
    for i, (leaf, to) in enumerate(zip(leaves, types, strict=True)):
        if to is not None:
            leaf = jnp.asarray(leaf).astype(to)
            ahead.append(leaf)
            if len(ahead) > _CASTS_AHEAD:
                jax.block_until_ready(ahead.popleft())
        if rows is not None and rows[i]:
            leaf = rows_on_lanes(leaf)
        out.append(leaf)
    jax.block_until_ready(list(ahead))
    return treedef.unflatten(out)


def cast_tree(variables: dict, dtype) -> dict:
    """The tree with every floating leaf held in ``dtype`` on the device
    (``Config.serving_param_dtype``), leaf by leaf as :func:`cast_leaves`
    does. Quantized leaves and integer leaves pass through."""
    dtype = jnp.dtype(dtype)
    return cast_leaves(variables, [
        dtype if not _is_q(leaf) and is_floating(leaf) else None
        for leaf in jax.tree.leaves(variables, is_leaf=_is_q)])


# a use that is no cast: a gather that takes whole rows of a 2-d operand
# (a token lookup: every index picks one row, and the slice is all of it)
ROWS = "rows"


def _takes_rows(eqn) -> bool:
    operand = eqn.invars[0].aval
    dims = eqn.params["dimension_numbers"]
    return (len(operand.shape) == 2
            and tuple(eqn.params["slice_sizes"]) == (1, operand.shape[1])
            and tuple(dims.start_index_map) == (0,)
            and tuple(dims.collapsed_slice_dims) == (0,))


def _note_uses(jaxpr, which: dict, uses: list) -> None:
    """Into ``uses[i]`` what ``jaxpr`` does with the variable ``which`` maps
    to ``i``: the type a ``convert_element_type`` gives it, :data:`ROWS`
    where it is the operand of a gather of whole rows, None for any other
    use (being an output is one). An operand of a nested jit and a
    constant of a scan ARE the value inside, so they are followed into the
    inner program; every other equation that holds a jaxpr is a use."""
    from jax.extend.core import Var

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        inner, followed = None, 0
        if name in ("jit", "pjit"):
            inner, followed = eqn.params["jaxpr"].jaxpr, len(eqn.invars)
        elif name == "scan":
            inner, followed = (eqn.params["jaxpr"].jaxpr,
                               eqn.params["num_consts"])
        passed = {}
        for i, var in enumerate(eqn.invars):
            leaf = which.get(var) if isinstance(var, Var) else None
            if leaf is None:
                continue
            if i < followed:
                passed[inner.invars[i]] = leaf
            elif name == "convert_element_type":
                uses[leaf].append(jnp.dtype(eqn.params["new_dtype"]))
            elif name == "gather" and i == 0 and _takes_rows(eqn):
                uses[leaf].append(ROWS)
            else:
                uses[leaf].append(None)
        if passed:
            _note_uses(inner, passed, uses)
    for var in jaxpr.outvars:
        if isinstance(var, Var) and var in which:
            uses[which[var]].append(None)


def leaf_uses(jaxpr, leaves: int) -> list:
    """What ``jaxpr`` does with each of its first ``leaves`` inputs, a list
    an input (``_note_uses`` says of what)."""
    uses = [[] for _ in range(leaves)]
    _note_uses(jaxpr, {v: i for i, v in enumerate(jaxpr.invars[:leaves])},
               uses)
    return uses


def narrowing_casts(jaxpr, leaves: int) -> list:
    """For each of the first ``leaves`` inputs of ``jaxpr``: the narrower
    floating type EVERY use of it casts it to, one and the same, or None.
    A floating input with such a type can be held in it and the program
    computes the same bits: what enters each product is the cast's result
    either way. An input that is used any other way, cast to two types, not
    used, not floating, or handed to something this cannot see through
    (``_note_uses``) gets None: the answer errs to the type it has."""
    out = []
    for var, seen in zip(jaxpr.invars, leaf_uses(jaxpr, leaves)):
        to = seen[0] if len(set(seen)) == 1 else None
        narrower = (isinstance(to, np.dtype) and is_floating(var.aval)
                    and jnp.issubdtype(to, jnp.floating)
                    and to.itemsize < var.aval.dtype.itemsize)
        out.append(to if narrower else None)
    return out


def gathered_rows(jaxpr, leaves: int) -> list:
    """For each of the first ``leaves`` inputs of ``jaxpr``: whether all the
    program does with it is gather whole rows of it (a token table under
    its lookup). Such a leaf is held with a row contiguous on the lanes
    (:func:`rows_on_lanes`), which is what the gather reads. A table that
    something else reads too (a tied head's product) is not named: the
    answer errs to the leaf as it is."""
    return [bool(seen) and all(use is ROWS for use in seen)
            for seen in leaf_uses(jaxpr, leaves)]


def held_as(module, variables: dict) -> tuple:
    """(the type to hold each leaf of a token-in LM's ``variables`` in, None
    where it stays as it is; whether the leaf is held with its rows on the
    lanes), both in tree order: the module's forward is traced abstractly
    (shapes and types of the leaves; no device memory, no arithmetic) and
    :func:`narrowing_casts` and :func:`gathered_rows` asked what it does
    with each leaf. A module computing in bfloat16 over float32 parameters
    casts its products' kernels and biases and nothing else; one computing
    in its parameters' type casts nothing; either gathers the rows of its
    token table and of nothing else. What is traced is a one-token decode
    apply (what ``models.generation.init_cache`` sizes a cache with): every
    layer goes through the one traced block (models/gpt.py
    ``_decode_block``), a tenth of the plain forward's trace at 36 layers;
    a model that decodes only through pages (latent attention) refuses it
    and is traced by its plain forward over eight tokens. Either stands for
    the engines' programs, which run the same layers' casts and the same
    lookup (tests/test_held_types.py reads the paged engine's own). Raises
    what the plain forward's trace raises (a module that takes no tokens)."""
    abstract = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(np.shape(l), l.dtype), variables)

    def traced(length, **kw):
        return jax.make_jaxpr(lambda p, t: module.apply(p, t, **kw))(
            abstract, jax.ShapeDtypeStruct((1, length), jnp.int32)).jaxpr

    try:
        jaxpr = traced(1, decode=True, mutable=["cache"])
    except Exception:
        jaxpr = traced(8)
    leaves = len(jax.tree.leaves(abstract))
    return narrowing_casts(jaxpr, leaves), gathered_rows(jaxpr, leaves)


def quantized_dot(x, qt: QuantizedTensor, *, dtype=None, impl: str = None):
    """``x @ dequant(qt)`` WITHOUT materializing the dense weight: the
    contraction runs on the int8 values and the per-output-channel scale
    multiplies the f32 accumulator afterward (exact reassociation — the
    scale is constant along the contracted axis). This is the apply hook
    the quantized decode path routes every dense projection through
    (models/layers.py ``QuantizableDense``).

    ``impl`` selects the implementation (default: the process config's
    ``int8_matmul_impl``): ``"auto"`` = Pallas kernel on TPU /
    ``dot_general`` elsewhere, ``"pallas"`` = force the kernel (interpret
    mode off-TPU — the CPU test path), ``"dot"`` = force the XLA
    fallback. Only 2-d quantized kernels (dense projections) are
    supported — a >2-d leaf (an MoE expert stack) has no well-defined
    last-axis contraction here and raises instead of computing garbage.
    ``dtype`` is the output dtype (default ``x.dtype``); accumulation is
    f32 in every impl."""
    if qt.q.ndim != 2:
        raise ValueError(
            f"quantized_dot wants a 2-d quantized kernel, got shape "
            f"{qt.q.shape} — route >2-d leaves (expert stacks) through the "
            f"dequantize path instead")
    if impl is None:
        from ..api.config import get_config

        impl = get_config().int8_matmul_impl
    if impl not in ("auto", "pallas", "dot"):
        raise ValueError(f"unknown int8 matmul impl {impl!r} "
                         f"(valid: 'auto', 'pallas', 'dot')")
    from ..ops.int8_matmul import int8_dot, int8_matmul

    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "dot"
    if impl == "pallas":
        return int8_matmul(x, qt.q, qt.s, out_dtype=dtype or x.dtype)
    return int8_dot(x, qt.q, qt.s, out_dtype=dtype or x.dtype)


INT8_TAG = "final-int8"


def quantize_final_checkpoint(job_id: str, flat_store, sharded_store,
                              registry=None) -> str:
    """OFFLINE quantization of a job's final checkpoint: read the FRESHEST
    final export (flat vs sharded resolved by mtime, the same rule serving
    uses — a retrain must never quantize a stale form; the sharded path
    assembles host-side on the control-plane host, not the serving chip),
    quantize the weight leaves, and write the storage-form tree under the
    ``final-int8`` tag in the same store form. Serving with
    ``KUBEML_SERVING_QUANTIZE=int8`` then PREFERS this tag (when it is at
    least as fresh as the dense final) and restores int8 straight onto the
    serving mesh — no dense transient on the chip. Returns "flat" or
    "sharded" (the form written).

    ``registry`` resolves the job's function so a training-layout
    checkpoint (pipeline stage-stacked) re-layouts to its SERVING shape
    BEFORE quantizing — per-stage slices of stacked SCALES do not exist,
    and the served module consumes flat blocks. A function that cannot be
    loaded is an ERROR, not a silent skip: quantizing the wrong layout
    would serve garbage with no error at quantize time."""
    from ..api.errors import CheckpointNotFoundError, KubeMLError
    from ..storage.checkpoint import FINAL_TAG

    flat_mtime = sharded_mtime = None
    try:
        flat_mtime = flat_store.export_path(
            job_id, tag=FINAL_TAG).stat().st_mtime_ns
    except Exception:
        pass
    try:
        sharded_mtime = sharded_store.manifest_path(
            job_id, FINAL_TAG).stat().st_mtime_ns
    except Exception:
        pass
    if flat_mtime is None and sharded_mtime is None:
        raise CheckpointNotFoundError(job_id)
    if sharded_mtime is None or (flat_mtime is not None
                                 and flat_mtime >= sharded_mtime):
        ck = flat_store.restore(job_id, tag=FINAL_TAG)
        form = "flat"
    else:
        ck = sharded_store.restore(job_id, FINAL_TAG)  # host leaves
        form = "sharded"
    variables = ck.variables
    if registry is not None:
        fn_name = ck.meta.get("request", {}).get("function_name", "")
        try:
            model = registry.load(fn_name)
        except Exception as e:
            raise KubeMLError(
                f"quantize needs job {job_id}'s function {fn_name!r} to "
                f"determine the serving layout, but loading it failed: {e}",
                400)
        remap = model.serving_remap()
        if remap is not None:
            from ..storage.sharded_checkpoint import apply_remap_host

            variables = apply_remap_host(variables, remap)
    storage = to_storage_tree(quantize_tree(variables))
    meta = {**ck.meta, "quantized": "int8", "layout": "serving"}
    if form == "flat":
        flat_store.save(job_id, storage, epoch=ck.epoch, tag=INT8_TAG,
                        meta=meta)
    else:
        sharded_store.save(job_id, storage, epoch=ck.epoch, tag=INT8_TAG,
                           meta=meta)
    return form


def quality_report(module, variables, tokens) -> dict:
    """Teacher-forced quality delta of int8 weights on a token batch: the
    bound the serving knob is published with (VERDICT r4 next-2 'bounded
    quality delta'). Returns max-abs and relative-L2 logits error plus
    top-1 (greedy next-token) agreement between full and int8 weights."""
    import flax.linen as nn

    dense = nn.meta.unbox(variables)
    tokens = jnp.asarray(tokens, jnp.int32)
    ref = module.apply(dense, tokens, train=False).astype(jnp.float32)
    qd = dequantize_tree(quantize_tree(variables), jnp.float32)
    quant = module.apply(qd, tokens, train=False).astype(jnp.float32)
    diff = jnp.abs(ref - quant)
    agree = jnp.mean(
        (jnp.argmax(ref, -1) == jnp.argmax(quant, -1)).astype(jnp.float32))
    return {
        "max_abs_err": float(jnp.max(diff)),
        "rel_l2_err": float(jnp.linalg.norm(diff.ravel())
                            / jnp.maximum(jnp.linalg.norm(ref.ravel()), 1e-9)),
        "top1_agreement": float(agree),
    }


# checkpoint-storage form: QuantizedTensor nodes become a marker dict so
# the (dict-recursing) checkpoint stores persist them unchanged — and a
# sharded restore can place q/s straight onto the serving mesh with no
# dense transient (round 5's "quantized checkpoint storage" follow-up)
Q8_Q = "__q8_q__"
Q8_S = "__q8_s__"


def to_storage_tree(variables: dict) -> dict:
    """QuantizedTensor nodes -> ``{Q8_Q: int8, Q8_S: scales}`` dicts (a
    plain dict pytree both checkpoint stores persist as-is)."""

    def one(leaf):
        if _is_q(leaf):
            return {Q8_Q: leaf.q, Q8_S: leaf.s}
        return leaf

    return jax.tree.map(one, variables, is_leaf=_is_q)


def _is_storage_q(node) -> bool:
    return isinstance(node, dict) and set(node) == {Q8_Q, Q8_S}


def from_storage_tree(tree: dict) -> dict:
    """Inverse of :func:`to_storage_tree`."""

    def one(node):
        if _is_storage_q(node):
            return QuantizedTensor(q=node[Q8_Q], s=node[Q8_S])
        return node

    return jax.tree.map(one, tree, is_leaf=_is_storage_q)


def is_quantized_tree(variables: dict) -> bool:
    """True when the tree carries live QuantizedTensor leaves."""
    return any(_is_q(l) for l in jax.tree.leaves(variables, is_leaf=_is_q))


def is_quantized_storage(tree: dict) -> bool:
    """True when a restored variables tree carries int8 storage markers."""
    return any(_is_storage_q(n)
               for n in jax.tree.leaves(tree, is_leaf=_is_storage_q))


def quantized_bytes(variables: dict) -> int:
    """Weight bytes the decode step STREAMS per token with this tree (the
    HBM-traffic accounting the speedup claim rests on). Embedding tables
    are excluded — decode gathers one row per table per token, so their
    full size never transits per step in either mode."""
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            variables, is_leaf=_is_q):
        if _gather_accessed(path):
            continue
        if _is_q(leaf):
            total += leaf.q.size * 1 + leaf.s.size * 4
        elif hasattr(leaf, "size"):
            total += leaf.size * np.dtype(leaf.dtype).itemsize
    return total
