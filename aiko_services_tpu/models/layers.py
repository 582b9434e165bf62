# Shared neural-net building blocks: functional jax, param pytrees, and
# logical sharding axes.
#
# No reference counterpart — the reference wraps external CUDA models
# (WhisperX: examples/speech/speech_elements.py:174-180; its framework code
# contains no model math).  Style: every block is a pair of pure functions
# (init(key, ...) -> params, apply(params, x, ...)) plus an axes() tree of
# logical axis names consumed by parallel.shard_pytree, so any model built
# from these blocks is sharding-annotated by construction.
#
# dtype policy: params live in float32 (or bfloat16 for serving), compute
# runs in the dtype of the activations, matmul accumulation is always
# float32 (preferred_element_type) — the MXU-native recipe.

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "linear_init", "linear", "linear_axes",
    "layer_norm_init", "layer_norm", "layer_norm_axes",
    "rms_norm_init", "rms_norm", "rms_norm_axes",
    "embedding_init", "embedding", "embedding_axes",
    "conv1d_init", "conv1d", "conv1d_axes", "conv_tail",
    "mha_init", "mha", "mha_axes", "precompute_kv", "init_kv_cache",
    "update_kv_cache", "quantize_linear", "quantize_linear_tree",
    "quantize_kv_cache", "dequantize_kv_cache",
    "slice_kv_rows", "split_kv_blocks", "concat_kv_rows",
    "kv_rows_nbytes",
    "gather_paged_kv", "scatter_paged_rows", "write_paged_blocks",
    "write_paged_runs", "writes_runs_by_blocks", "run_blocks",
    "slice_paged_block",
    "linear_logits",
    "sinusoid_position_encoding", "gelu", "rope_frequencies", "apply_rope",
]


# -- linear ------------------------------------------------------------------

def linear_init(key, in_dim: int, out_dim: int, bias: bool = True,
                dtype=jnp.float32, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    params = {"w": (jax.random.normal(key, (in_dim, out_dim)) *
                    scale).astype(dtype)}
    if bias:
        params["b"] = jnp.zeros((out_dim,), dtype)
    return params


def linear(params, x):
    if "w8" in params:
        # weight-only int8 (quantize_linear): the int8->activation-dtype
        # convert is the dot operand (fuses — no materialized copy) and
        # the per-output-channel scale lands exactly on the f32
        # accumulator: y = (x @ W8) * s + b is exact algebra, not an
        # approximation of the dequantized matmul
        y = jnp.einsum("...i,io->...o", x, params["w8"].astype(x.dtype),
                       preferred_element_type=jnp.float32) * params["s"]
    else:
        y = jnp.einsum("...i,io->...o", x, params["w"],
                       preferred_element_type=jnp.float32)
    if "b" in params:
        y = y + params["b"]
    return y.astype(x.dtype)


def linear_axes(in_axis: str, out_axis: str, bias: bool = True):
    axes = {"w": (in_axis, out_axis)}
    if bias:
        axes["b"] = (out_axis,)
    return axes


def linear_logits(params, x):
    """Vocab/classifier projection kept in f32 — no activation-dtype
    downcast, because rounding logits to bf16 before an argmax can
    flip near-ties against an f32 oracle.  Consumes plain {"w"} or
    quantized {"w8", "s"} linears: besides linear(), this is the ONLY
    place the weight-quantized format is interpreted, so format
    changes stay in this module."""
    if "w8" in params:
        logits = jnp.einsum("...d,dv->...v", x,
                            params["w8"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return logits * params["s"]
    return jnp.einsum("...d,dv->...v", x, params["w"],
                      preferred_element_type=jnp.float32)


def quantize_linear(params):
    """Weight-only int8 for a linear: one f32 scale per OUTPUT channel
    (max|w| over the input axis), so y = (x @ W8) * s + b reproduces
    the bf16 matmul up to int8 rounding of the weights — activations
    stay full precision (W8A16).

    Half the weights' bytes in memory; what it does to a decode
    step's time no cell has measured on this installation.  Returns
    {"w8": int8 [in,out], "s": f32 [out]} (+"b" passthrough), which
    linear() consumes transparently."""
    w = params["w"]
    scale = (jnp.max(jnp.abs(w), axis=0).astype(jnp.float32) / 127.0
             + 1e-12)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    out = {"w8": q, "s": scale}
    if "b" in params:
        out["b"] = params["b"]
    return out


def quantize_linear_tree(params, exclude=("router",)):
    """Recursively replace every linear param dict ({"w": 2-D, ["b"]})
    in a pytree with its quantize_linear form.  Leaves everything else
    untouched: conv1d ("w" is 3-D), embeddings ("table"), norms
    (scale/bias), bare arrays.  Keys in `exclude` are skipped whole —
    the default skips MoE routers, where int8 rounding could flip
    top-k expert selection for negligible byte savings."""
    def walk(node):
        if isinstance(node, dict):
            if "w" in node and getattr(node["w"], "ndim", 0) == 2 \
                    and set(node) <= {"w", "b"}:
                return quantize_linear(node)
            return {key: (value if key in exclude else walk(value))
                    for key, value in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(value) for value in node)
        return node
    return walk(params)


# -- norms -------------------------------------------------------------------

def layer_norm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,),
                                                                dtype)}


def layer_norm(params, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


def layer_norm_axes():
    return {"scale": ("embed",), "bias": ("embed",)}


def rms_norm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rms_norm(params, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * params["scale"]).astype(x.dtype)


def rms_norm_axes():
    return {"scale": ("embed",)}


# -- embedding ---------------------------------------------------------------

def embedding_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, dim)) *
                      0.02).astype(dtype)}


def embedding(params, token_ids):
    return jnp.take(params["table"], token_ids, axis=0)


def embedding_axes():
    return {"table": ("vocab", "embed")}


# -- conv1d ------------------------------------------------------------------

def conv1d_init(key, in_ch: int, out_ch: int, kernel: int,
                dtype=jnp.float32):
    scale = 1.0 / math.sqrt(in_ch * kernel)
    return {"w": (jax.random.normal(key, (kernel, in_ch, out_ch)) *
                  scale).astype(dtype),
            "b": jnp.zeros((out_ch,), dtype)}


def conv1d(params, x, stride: int = 1, padding=None):
    """x: [B, T, C_in] → [B, T', C_out] (maps onto the MXU as a matmul
    over the unrolled kernel window).

    Default padding is SYMMETRIC (k-1)//2 both sides — torch Conv1d's
    `padding=k//2` convention, which whisper checkpoints are trained
    under.  XLA's "SAME" pads asymmetrically under stride>1 (left 0 /
    right 1 for k=3, s=2), silently shifting every strided frame by one
    sample relative to the checkpoint.  The symmetric default only
    preserves length for ODD kernels; even kernels must pass an
    explicit `padding`."""
    if padding is None:
        k = params["w"].shape[0]
        if k % 2 == 0:
            raise ValueError(
                f"conv1d default padding requires an odd kernel, got "
                f"{k}; pass padding explicitly for even kernels")
        padding = [((k - 1) // 2, (k - 1) // 2)]
    y = jax.lax.conv_general_dilated(
        x, params["w"], window_strides=(stride,), padding=padding,
        dimension_numbers=("NWC", "WIO", "NWC"),
        preferred_element_type=jnp.float32)
    return (y + params["b"]).astype(x.dtype)


def conv1d_axes():
    return {"w": (None, None, "embed"), "b": ("embed",)}


def conv_tail(pre, tail, weights, bias, live):
    """A recurrent layer's short causal convolution a channel over a block
    of a slot's tokens, and the roll of the slot's tail.  pre [A, T, C] the
    block's inputs, tail [A, (conv-1) x C] the inputs of the conv-1
    positions before position 0 of pre, oldest first, side by side on the
    lanes (C a whole number of lane tiles at the published widths: a tap is
    an aligned window, where a [conv-1, C] tail is three rows of a tile
    that XLA lays out anew around every use), weights [conv, C], bias [C]
    or None, live [A, T] bool (live positions lead each row) ->
    (silu(conv) f32 [A, T, C], the new tail: the inputs of the last conv-1
    LIVE positions, in tail's dtype).

    The form is read off pre's SHAPE.  One token (every decode step): the
    taps are the tail's windows and the token, each its own [A, C] slab,
    and the roll is one select between the tail shifted by a window and
    the tail as it was: no [A, conv, C] array, no gather, and a slot that
    does not decode keeps its tail.  A prompt's piece: the taps are windows
    of tail and piece laid end to end, and the new tail starts at the row's
    count of live positions; one row slices once, several a row at a
    time."""
    rows, t, channels = pre.shape
    taps = weights.shape[0]
    weights = weights.astype(jnp.float32)
    held = tail.astype(pre.dtype)
    if t == 1:
        windows = [held[:, i * channels:(i + 1) * channels][:, None]
                   for i in range(taps - 1)] + [pre]
        rolled = jnp.where(live, jnp.concatenate(
            [held[:, channels:], pre[:, 0]], axis=1), held)
    else:
        full = jnp.concatenate(
            [held.reshape(rows, taps - 1, channels), pre], axis=1)
        windows = [full[:, i:i + t] for i in range(taps)]
        count = live.sum(axis=1).astype(jnp.int32)
        if rows == 1:
            rolled = jax.lax.dynamic_slice_in_dim(
                full[0], count[0], taps - 1, axis=0)[None]
        else:
            rolled = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
                row, n, taps - 1, axis=0))(full, count)
        rolled = rolled.reshape(tail.shape)
    mixed = sum(window.astype(jnp.float32) * weights[i]
                for i, window in enumerate(windows))
    if bias is not None:
        mixed = mixed + bias.astype(jnp.float32)
    return jax.nn.silu(mixed), rolled.astype(tail.dtype)


# -- attention ---------------------------------------------------------------

def mha_init(key, dim: int, num_heads: int, num_kv_heads: int | None = None,
             bias: bool = True, dtype=jnp.float32):
    """Multi-head attention params.  num_kv_heads < num_heads = GQA."""
    num_kv_heads = num_kv_heads or num_heads
    head_dim = dim // num_heads
    keys = jax.random.split(key, 4)
    return {
        "q": linear_init(keys[0], dim, num_heads * head_dim, bias, dtype),
        "k": linear_init(keys[1], dim, num_kv_heads * head_dim, False,
                         dtype),
        "v": linear_init(keys[2], dim, num_kv_heads * head_dim, bias,
                         dtype),
        "o": linear_init(keys[3], num_heads * head_dim, dim, bias, dtype),
    }


def mha_axes(bias: bool = True):
    return {
        "q": linear_axes("embed", "heads", bias),
        "k": linear_axes("embed", "kv_heads", False),
        "v": linear_axes("embed", "kv_heads", bias),
        "o": linear_axes("heads", "embed", bias),
    }


def _split_heads(x, num_heads):
    b, t, _ = x.shape
    return x.reshape(b, t, num_heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def init_kv_cache(batch: int, max_len: int, num_kv_heads: int,
                  head_dim: int, dtype=jnp.float32):
    """Static-shape KV cache: [B, H_kv, T_max, D] + write index."""
    shape = (batch, num_kv_heads, max_len, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "index": jnp.zeros((), jnp.int32)}


def update_kv_cache(cache, k_new, v_new):
    """Write new K/V at the cache cursor (static shapes; donation-friendly
    under jit so decode steps update in place on TPU)."""
    index = cache["index"]
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, index,
                                            axis=2)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, index,
                                            axis=2)
    return {"k": k, "v": v, "index": index + k_new.shape[2]}


def precompute_kv(params, kv_input, num_kv_heads: int):
    """Project K/V once for reuse across many queries (e.g. encoder output
    attended by every decode step).  Returns (k, v): [B, H_kv, T, D]."""
    k = _split_heads(linear(params["k"], kv_input), num_kv_heads)
    v = _split_heads(linear(params["v"], kv_input), num_kv_heads)
    return k, v


def quantize_kv(tensor, mode: str = "position"):
    """Symmetric int8 quantization of a K or V tensor [..., T, D].
    Halves the HBM footprint of a precomputed KV cache — and, in
    "tensor" mode, halves the decode tail's dominant read.

    mode="position": scale over the last axis (per-position, bf16
    scales).  Finer-grained, but the dequant is a broadcast MULTIPLY —
    measured in-program, XLA re-materializes the dequantized bf16 KV
    every scan step and throughput LOSES ~24%.  Memory lever only.

    mode="tensor": ONE f32 scale per leading-axis element (per batch
    item for a [B, H, T, D] cache — NOT one global scalar: a single
    loud co-batched stream would coarsen every other stream's
    quantization and make transcripts depend on batch composition).
    The scale is constant along the head/position/feature axes, so
    the dequant is a bare int8→bf16 convert as the dot operand (mha
    folds the scale into the softmax scale / output as a per-batch
    broadcast), which XLA fuses instead of materializing.  Coarser
    scale than "position", so slightly larger error.

    Returns {"q": int8, "s": scale} — dequantize_kv handles both
    (the scale broadcasts)."""
    if mode == "tensor":
        axes = tuple(range(1, tensor.ndim))
        scale = (jnp.max(jnp.abs(tensor), axis=axes, keepdims=True)
                 .astype(jnp.float32) / 127.0 + 1e-12)
        q = jnp.clip(jnp.round(tensor.astype(jnp.float32) / scale),
                     -127, 127).astype(jnp.int8)
        return {"q": q, "s": scale}
    if mode != "position":
        raise ValueError(f"unknown quantize_kv mode {mode!r}")
    scale = (jnp.max(jnp.abs(tensor), axis=-1, keepdims=True)
             .astype(jnp.float32) / 127.0 + 1e-12).astype(jnp.bfloat16)
    q = jnp.clip(jnp.round(tensor.astype(jnp.float32) /
                           scale.astype(jnp.float32)),
                 -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def dequantize_kv(kv, dtype):
    """Inverse of quantize_kv; passes plain arrays through."""
    if isinstance(kv, dict) and "q" in kv:
        return (kv["q"].astype(dtype) * kv["s"].astype(dtype))
    return kv


def quantize_kv_cache(tensor):
    """Symmetric int8 for the SERVING KV cache (continuous batching):
    one f32 scale per (..., position) — for a [S, H, T, D] slot cache
    that is per (slot, head, position), the finest grain whose dequant
    still FOLDS instead of materializing.  Unlike quantize_kv's
    "position" mode (a [..., T, 1] broadcast-multiply the decode scan
    re-materializes every step, measured −24%), this scale's shape
    [..., T] is consumed by serving's decode attention as a fold along
    the score/weight time axis: scores·s_k on the QK pass and
    weights·s_v before the PV pass — exact algebra, so the int8 buffer
    stays the dot operand (the convert fuses) and the cache read is
    halved, which is the HBM-bound decode step's dominant byte.

    Returns {"q": int8 [..., T, D], "s": f32 [..., T]}."""
    scale = (jnp.max(jnp.abs(tensor), axis=-1).astype(jnp.float32)
             / 127.0 + 1e-12)
    q = jnp.clip(jnp.round(tensor.astype(jnp.float32) /
                           scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def dequantize_kv_cache(kv, dtype):
    """Inverse of quantize_kv_cache; passes plain arrays through.  The
    materializing path — serving's prefill-extend uses it OFF the
    decode critical path; the decode scan folds instead."""
    if isinstance(kv, dict) and "q" in kv:
        return kv["q"].astype(dtype) * kv["s"][..., None].astype(dtype)
    return kv


def slice_kv_rows(cache, slot, start: int, stop: int):
    """One slot's K/V rows [start, stop) from a SERVING slot cache leaf
    — a plain [S, H, T, D] array or the int8 serving form
    {"q" int8 [S, H, T, D], "s" f32 [S, H, T]} (quantize_kv_cache).
    Returns [H, t, D] (or the dict with s [H, t]) as a device-side
    slice COPY: the harvest read behind serving's prefix/KV reuse
    cache.  Slicing the quantized form keeps q and s together, so a
    cached block stores exactly the bytes decode would read — a later
    hit is a bytes win AND bit-faithful to the donor's cache."""
    if isinstance(cache, dict):
        return {"q": cache["q"][slot, :, start:stop],
                "s": cache["s"][slot, :, start:stop]}
    return cache[slot, :, start:stop]


def split_kv_blocks(rows, block_tokens: int):
    """Split harvested rows [H, n*B, D] (or the quantized dict form)
    into n per-block leaves [H, B, D] along the time axis — the unit
    the prefix cache stores and hash-addresses."""
    if isinstance(rows, dict):
        count = rows["q"].shape[1] // block_tokens
        return [{"q": rows["q"][:, i * block_tokens:
                                (i + 1) * block_tokens],
                 "s": rows["s"][:, i * block_tokens:
                                (i + 1) * block_tokens]}
                for i in range(count)]
    count = rows.shape[1] // block_tokens
    return [rows[:, i * block_tokens:(i + 1) * block_tokens]
            for i in range(count)]


def concat_kv_rows(blocks):
    """Concatenate per-block K/V leaves back into contiguous rows along
    the time axis (inverse of split_kv_blocks) — the copy-in side of a
    prefix-cache hit.  Handles the quantized dict form leaf-wise so an
    int8 chain lands in the slot cache without a dequantize/requantize
    round trip (no double rounding)."""
    if isinstance(blocks[0], dict):
        return {"q": jnp.concatenate([b["q"] for b in blocks], axis=1),
                "s": jnp.concatenate([b["s"] for b in blocks], axis=1)}
    return jnp.concatenate(blocks, axis=1)


def kv_rows_nbytes(rows) -> int:
    """Accounting bytes of one K or V rows leaf (array or quantized
    dict) — the prefix cache's budget currency."""
    return int(sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree_util.tree_leaves(rows)))


# -- paged KV block pool primitives (ISSUE 15) -------------------------------
# The paged serving cache (serving_paged.BlockPool) stores KV in one
# [N, H, B, D] pool of fixed B-token blocks per layer (int8 pools carry
# the {"q" i8 [N, H, B, D], "s" f32 [N, H, B]} serving form), addressed
# by per-slot int32 block tables.  These primitives are the whole
# device-side vocabulary of the paged path: a gather that materializes
# a slot-major [S, H, T, D] view for the attention einsums (the one
# place paged and dense numerics must agree BIT-for-bit — the gathered
# view is value-identical to the dense slot cache, so every attention
# body downstream is shared, not forked), a per-position scatter for
# the decode round's side-buffer merge, a whole-block scatter for the
# admit prefill, a block slice read for harvest-free wire shipping,
# and a plane split for the pallas paged-attention kernel (ISSUE 16),
# which reads pool blocks straight through the table and demotes the
# gather to the bit-parity oracle role.  Out-of-range destination ids
# drop (mode="drop") — the paged analogue of the dense path's
# _POS_INVALID discipline.

def gather_paged_kv(pool, tables):
    """Assemble a slot-major KV view from a block pool: `tables` is
    [S, nb] int32 block ids; returns [S, H, nb*B, D] (or the int8 dict
    with s [S, H, nb*B]).  Position p of slot s reads
    pool[tables[s, p // B], :, p % B] — the block-table indirection of
    vLLM's PagedAttention, expressed as an XLA gather.  The gather
    materializes once per compiled program (hoisted out of the decode
    scan: the main cache is read-only through a round), so the scan's
    per-step HBM traffic is identical to the dense cache's."""
    if isinstance(pool, dict):
        return {"q": gather_paged_kv(pool["q"], tables),
                "s": gather_paged_kv(pool["s"], tables)}
    g = jnp.take(pool, tables, axis=0)     # [S, nb, H, B, ...]
    if g.ndim == 5:                        # values [S, nb, H, B, D]
        s, nb, h, b, d = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(s, h, nb * b, d)
    s, nb, h, b = g.shape                  # scales [S, nb, H, B]
    return g.transpose(0, 2, 1, 3).reshape(s, h, nb * b)


def paged_pool_planes(pool):
    """(value plane, scale plane or None) for one paged-pool leaf —
    the int8 serving dict splits into its i8 values [N, H, B, D] and
    f32 per-position scales [N, H, B] (separate DMA operands for the
    pallas paged-attention kernel); native pools carry no scale.  The
    pool-grain sibling of serving._kv_planes, kept here so the int8
    pool layout is decoded in exactly one module."""
    if isinstance(pool, dict):
        return pool["q"], pool["s"]
    return pool, None


def scatter_paged_rows(pool, dest_blocks, offsets, rows):
    """Scatter per-position rows into pool blocks: rows is
    [S, H, W, D] (or the scale form [S, H, W]); dest_blocks/offsets are
    [S, W] — row (s, w) lands at pool[dest_blocks[s, w], :,
    offsets[s, w]].  Out-of-range dest ids DROP (inactive slots,
    rejected speculative drafts, positions past the table) instead of
    clamping into a live block.

    The heads axis is INDEXED, not sliced, so that the scatter's
    window is one contiguous D row of the [N, H, B, D] layout (a
    scalar of the scale plane) and XLA:TPU updates the donated leaf in
    place.  With heads sliced between the two indexed axes the window
    is strided, and the compiler copies the whole leaf into a layout
    with heads and offsets swapped and back again, 2 x 0.3 ms a
    100 MB leaf; tests/test_chip_compile.py holds the in-place form."""
    if isinstance(pool, dict):
        return {"q": scatter_paged_rows(pool["q"], dest_blocks,
                                        offsets, rows["q"]),
                "s": scatter_paged_rows(pool["s"], dest_blocks,
                                        offsets, rows["s"])}
    if rows.ndim == 4:                     # values [S, H, W, D]
        vals = rows.transpose(0, 2, 1, 3)  # [S, W, H, D]
    else:                                  # scales [S, H, W]
        vals = rows.transpose(0, 2, 1)     # [S, W, H]
    heads = jnp.arange(pool.shape[1])
    return pool.at[dest_blocks[:, :, None], heads,
                   offsets[:, :, None]].set(vals, mode="drop")


def run_blocks(width: int, block_tokens: int) -> int:
    """How many blocks a run of `width` consecutive rows can fall in,
    wherever it starts: the images write_paged_runs reads and writes
    back for a slot."""
    return (width - 1) // block_tokens + 2


def writes_runs_by_blocks(heads: int, width: int,
                          block_tokens: int) -> bool:
    """Whether a run of `width` rows a slot goes to the pool by whole
    blocks (write_paged_runs) or row by row (scatter_paged_rows), from
    the static shapes alone.  The TPU walks a gather or a scatter
    window by window, some 80 ns each whatever the window holds, so
    the form with fewer windows a slot wins; the block form's count
    twice, read and written back.  On the chip (PERF.md, PR 32; us a
    leaf): 8 heads x 4 rows of 24 slots 62 by rows and 18 by blocks,
    a chunk of 512 rows 281 and 34; ONE head x 4 rows of 32 slots (a
    latent leaf: 4 windows either way, and the blocks move 160 KiB
    for the rows' 5) 13 and 19, so a tie keeps the rows."""
    return 2 * run_blocks(width, block_tokens) < width * heads


# A run this short is laid into its images by one select a row, all
# fused into ONE pass over the images (24 slots: 18 us a leaf at 4
# rows, 22 at 8); a longer one, a chunk, by a slice update a slot on
# the images laid out position-major, which for 4 and 8 rows of 24
# slots took 87 us (PERF.md, PR 32: a scatter of 24 strided windows
# and two transposes of the images).
_SELECT_ROWS = 32


def write_paged_runs(pool, tables, starts, rows, live):
    """Write each slot's run of consecutive rows into the pool by
    WHOLE blocks: rows is [S, H, W, D] (or the scale form [S, H, W]),
    row (s, w) lands at position starts[s] + w of slot s, whose blocks
    tables[s] ([S, nb]) names; slots where `live` ([S]) is False, and
    positions past the table, drop.  The same write as
    scatter_paged_rows over destinations formed from the table, in
    (W - 1) // B + 2 scatter windows a slot where that has W x H:

    1. the ids of the blocks the run touches, out of range (they drop)
       for a slot that is not live, past the table, and past the run's
       last row — a short run inside one block sends ONE image of it;
    2. those blocks read from the pool, [S, nblk, H, B, D];
    3. the rows laid into the images at starts % B; every other cell
       of an image keeps the bits it was read with (int8 values and
       scales are quantized once, by the caller, and never again);
    4. the images written back by one whole-block scatter.

    The rows go into the small IMAGE and never into the pool: a loop
    of slice updates on the pool itself keeps two copies of it and is
    8-20 times slower than the row scatter (PERF.md, PR 25).  No two
    live slots may name one block in a run (the pool's rule for any
    write): each would write the block back without the other's rows."""
    if isinstance(pool, dict):
        return {"q": write_paged_runs(pool["q"], tables, starts,
                                      rows["q"], live),
                "s": write_paged_runs(pool["s"], tables, starts,
                                      rows["s"], live)}
    num_total, heads, block = pool.shape[:3]
    slots, width = rows.shape[0], rows.shape[2]
    nb = tables.shape[1]
    nblk = run_blocks(width, block)
    first = starts // block
    blocks = first[:, None] + jnp.arange(nblk)[None]           # [S, nblk]
    last = (starts + width - 1) // block
    ids = jnp.take_along_axis(tables, jnp.clip(blocks, 0, nb - 1), axis=1)
    written = live[:, None] & (blocks < nb) & (blocks <= last[:, None])
    images = jnp.take(pool, jnp.where(written, ids, 0), axis=0)
    offsets = starts - first * block                           # [S]
    rest = (1,) * (rows.ndim - 3)
    if width <= _SELECT_ROWS:
        # position of every image cell in its slot's run, or outside it
        place = (jnp.arange(nblk)[:, None] * block +
                 jnp.arange(block)[None])[None] - offsets[:, None, None]
        place = place.reshape((slots, nblk, 1, block) + rest)
        for w in range(width):
            row = rows[:, None, :, w:w + 1]        # [S, 1, H, 1, ...]
            images = jnp.where(place == w, row, images)
    else:
        flat = jnp.swapaxes(images, 1, 2).reshape(
            (slots, heads, nblk * block) + images.shape[4:])
        flat = jax.vmap(
            lambda image, run, offset:
            jax.lax.dynamic_update_slice_in_dim(image, run, offset,
                                                axis=1))(
            flat, rows.astype(pool.dtype), offsets)
        images = jnp.swapaxes(flat.reshape(
            (slots, heads, nblk, block) + images.shape[4:]), 1, 2)
    return pool.at[jnp.where(written, ids, num_total)].set(
        images.astype(pool.dtype), mode="drop")


def write_paged_blocks(pool, block_ids, rows):
    """Whole-block scatter for the admit prefill: rows is
    [A, H, nb*B, D] (or scales [A, H, nb*B]) covering nb =
    block_ids.shape[1] complete blocks per admit row; each block lands
    at pool[block_ids[a, j]].  Invalid rows carry out-of-range ids and
    drop."""
    if isinstance(pool, dict):
        return {"q": write_paged_blocks(pool["q"], block_ids,
                                        rows["q"]),
                "s": write_paged_blocks(pool["s"], block_ids,
                                        rows["s"])}
    nb = block_ids.shape[1]
    if rows.ndim == 4:
        a, h, t, d = rows.shape
        vals = rows.reshape(a, h, nb, t // nb, d).transpose(0, 2, 1, 3,
                                                            4)
    else:
        a, h, t = rows.shape
        vals = rows.reshape(a, h, nb, t // nb).transpose(0, 2, 1, 3)
    return pool.at[block_ids].set(vals, mode="drop")


def slice_paged_block(pool, block_id: int):
    """One block's rows [H, B, D] (or the int8 dict) from the pool —
    the read behind shipping a pool-resident cache block over the
    disaggregated wire.  A device-side slice view; np.asarray at the
    call site makes the host copy."""
    if isinstance(pool, dict):
        return {"q": pool["q"][block_id], "s": pool["s"][block_id]}
    return pool[block_id]


def mha(params, x, kv_input=None, mask=None, cache=None,
        num_heads: int = 8, num_kv_heads: int | None = None,
        qk_transform=None, precomputed_kv=None, fused: bool = True):
    """Attention: self (kv_input None), cross (kv_input or precomputed_kv),
    optional KV cache.

    mask: broadcastable to [B, H, Tq, Tk], True = attend.
    qk_transform(q, k) -> (q, k): applied after head split, before the
    cache write (RoPE hook — cached keys are stored already-positioned).
    precomputed_kv: (k, v) already projected+split (cross-attention cache).
    Returns (output, new_cache)."""
    num_kv_heads = num_kv_heads or num_heads
    q = _split_heads(linear(params["q"], x), num_heads)
    # mode="tensor"-quantized KV: keep the int8 buffer as the dot
    # operand (a bare convert fuses; a per-POSITION scale multiply
    # materializes a bf16 copy per decode step — measured −24%) and
    # fold the per-batch scales into the score scale / output.  A
    # scale qualifies for folding iff it is constant along every axis
    # but the batch one (scalar, or [B,1,...,1]).
    def _foldable(s):
        return jnp.ndim(s) == 0 or all(d == 1 for d in s.shape[1:])

    k_scale = v_scale = None
    if precomputed_kv is not None:
        k, v = precomputed_kv
        # fold only when BOTH k and v are quantized dicts with foldable
        # scales — a mixed pair (or a per-position v scale) must take
        # the dequantize path, not crash or mis-scale (ADVICE r5)
        if isinstance(k, dict) and isinstance(v, dict) and \
                _foldable(k["s"]) and _foldable(v["s"]):
            # scale shapes [B,1,1,1] broadcast against scores
            # [B,H,Tq,Tk] and output [B,H,Tq,D] directly
            k_scale, v_scale = k["s"], v["s"]
            k, v = k["q"].astype(x.dtype), v["q"].astype(x.dtype)
        else:
            k = dequantize_kv(k, x.dtype)
            v = dequantize_kv(v, x.dtype)
    else:
        k, v = precompute_kv(params, x if kv_input is None else kv_input,
                             num_kv_heads)
    if qk_transform is not None:
        q, k = qk_transform(q, k)

    if cache is not None:
        cache = update_kv_cache(cache, k, v)
        k, v = cache["k"], cache["v"]
        # valid-position mask for the unwritten cache tail
        valid = (jnp.arange(k.shape[2]) < cache["index"])[None, None, None]
        mask = valid if mask is None else (mask & valid)

    if num_kv_heads != num_heads:                  # GQA: repeat KV groups
        repeat = num_heads // num_kv_heads
        k = jnp.repeat(k, repeat, axis=1)
        v = jnp.repeat(v, repeat, axis=1)

    if fused and mask is None and cache is None and k_scale is None \
            and q.shape[2] == k.shape[2]:
        # mask-free self/cross attention: fused flash path (pallas on TPU
        # when shapes tile, XLA otherwise)
        from ..ops.attention import attention
        out = attention(q, k, v)
        return linear(params["o"], _merge_heads(out)), cache

    scale = 1.0 / math.sqrt(q.shape[-1])
    if k_scale is not None:
        scale = scale * k_scale
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, v,
                     preferred_element_type=jnp.float32)
    if v_scale is not None:
        out = out * v_scale
    out = out.astype(x.dtype)
    return linear(params["o"], _merge_heads(out)), cache


# -- positional encodings ----------------------------------------------------

def sinusoid_position_encoding(length: int, dim: int,
                               max_timescale: float = 10000.0):
    """Whisper-style sinusoids: [length, dim]."""
    half = dim // 2
    log_increment = math.log(max_timescale) / max(half - 1, 1)
    inv_timescales = jnp.exp(-log_increment * jnp.arange(half))
    scaled = jnp.arange(length)[:, None] * inv_timescales[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0):
    """RoPE cos/sin tables: each [max_len, head_dim//2]."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2) / head_dim))
    angles = jnp.arange(max_len)[:, None] * inv[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin, position_offset=0):
    """x: [B, H, T, D]; rotates pairs (even, odd) by position angle.

    position_offset: scalar (shared), or [B] vector — per-example
    offsets for continuous batching, where each slot sits at its own
    sequence position."""
    t = x.shape[2]
    offset = jnp.asarray(position_offset)
    if offset.ndim == 0:
        positions = offset + jnp.arange(t)                   # [T]
        cos_t = jnp.take(cos, positions, axis=0)[None, None]  # [1,1,T,D/2]
        sin_t = jnp.take(sin, positions, axis=0)[None, None]
    else:
        positions = offset[:, None] + jnp.arange(t)[None]    # [B, T]
        cos_t = jnp.take(cos, positions, axis=0)[:, None]    # [B,1,T,D/2]
        sin_t = jnp.take(sin, positions, axis=0)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rotated = jnp.stack([x1 * cos_t - x2 * sin_t,
                         x1 * sin_t + x2 * cos_t], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


def gelu(x):
    # exact (erf) gelu: what whisper/HF "gelu" checkpoints are trained
    # under — the tanh approximation drifts logits by ~5e-3, enough to
    # flip near-tie argmax decodes on real weights
    return jax.nn.gelu(x, approximate=False)
