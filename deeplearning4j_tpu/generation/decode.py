"""Decode-mode forwards: incremental single-token model evaluation over
donated device state.

Seven adapters expose one contract to the GenerationServer:

- **BertDecoder** — transformer stacks built on `models/bert.py` params:
  one K and one V cache leaf A LAYER, `(S, C, H·Dh)` (S slots, C =
  cache-length rung), with a rolling per-slot position index. Rows are
  major and the hidden width is the minor dimension — a whole number of
  128-lane tiles — so the donated state, the row write and the decode
  kernel all take a leaf as it lies and the compiled step never re-lays
  or slices the cache (with Dh = 64 minor, half a lane tile, the TPU
  picks a layout per use and copies the whole cache between them; a
  stacked layer axis adds a slice per layer). `step` embeds the current token at
  its slot position, writes its K/V row (S whole rows of H·Dh lanes),
  and attends the single query against the cached keys via
  `flash_attention_decode` (Pallas kernel on TPU, einsum elsewhere) —
  O(rows in use) work per token instead of the O(T²) full-sequence
  re-forward: over the dense float cache `step` hands the kernel the
  slots' rows in use (`lengths = min(pos + 1, C)`), and its grid holds
  the tiles of each leaf that those rows reach, not the rung (the paged
  and the int8 cache read as they did).
  `prefill` runs the causal full forward over a length-bucketed prompt
  and writes the whole `(P, H·Dh)` K/V block into the slot's cache rows
  in one shot.

- **RecurrentDecoder** — LSTM/GRU-style `MultiLayerNetwork`s
  (TextGenerationLSTM and friends): the decode state is the per-layer
  recurrent carry (h, c) rows, threaded through the network's own
  `_forward(carries=...)` path, so decode-step numerics are
  BIT-IDENTICAL to the full-sequence scan (tier-1 asserted).

- **NemotronHDecoder** — hybrid stacks built on `models/nemotron_h.py`
  params, whose donated cache holds TWO kinds of state side by side: K/V
  leaves `(S, C, Hkv·Dh)` for the attention layers, written and read as
  BertDecoder's are (grouped-query: the decode kernel maps a query head
  to its group's lanes), and for the Mamba-2 layers a float32 state leaf
  `(S, heads, head_dim, state)` and a convolution-tail leaf `(S, K-1,
  lanes)` that no rung touches. Its expert layers count what they compute
  on the device (`counter_names`).

- **KeyeDecoder** — stacks built on `models/keye_vl.py` params, whose
  cache holds THREE leaves a layer: K and V rows `(S, C, Hkv·Dh)` and the
  sparse-attention indexer's key rows, PACKED two positions a row, `(S, C
  / 2, 2·Di)`, written, grown and grafted together. (Di = 64 is half a
  lane tile, what the first paragraph warns of: the chip gave a `(S, C,
  64)` leaf a layout of its own and the compiled step copied every one
  whole, in and out. Packed, its minor dimension is one lane tile:
  `kernels/indexer.py`.) `step` scores every row in use against the index
  keys, finds the `topk` best exactly (`kernels/selection.py`) and runs
  the decode kernel over THOSE rows: the K and V leaves read in place
  under the selection as a mask, the tiles past a slot's position
  skipped, on a rung of up to `_IN_PLACE_RUNGS` x `topk` rows; on a longer
  one the kept rows gathered into a `(S, topk, Hkv·Dh)` rung first;
  `prefill` does the same selection for every prompt position. Its
  expert layers count as NemotronHDecoder's do, and the indexer counts the
  rows it scored and kept and the rows attention read for them.

- **MLADecoder** — stacks built on `models/deepseek_v3.py` params
  (multi-head latent attention), whose cache holds ONE leaf a layer and in
  it ONE row a position for all heads' keys AND values: the normed latent
  and the rotated key lanes, two positions a row, `(S, C / 2, 2·(L + R))`
  (`kernels/mla_attention.py` says why). `prefill` attends in the EXPANDED
  form (keys and values decompressed for the prompt) and grafts the latent
  rows; `step` attends in the ABSORBED form (the key up-projection folded
  into the query, the value up-projection applied after the softmax) and
  reads each row in use once. Two algebraically equal paths that meet in
  the cache. Its expert layers count as NemotronHDecoder's do, and the
  attention counts the rows it needed and the rows its kernel fetched.

- **AfmoeDecoder** — stacks built on `models/afmoe.py` params, whose
  layers are of two kinds and whose cache holds leaves of two LENGTHS: a
  sliding-window layer's K and V rows in a RING of `min(window, C)` rows
  (position p at row p mod window), which `grow` leaves alone once it has
  the window's rows, beside a full-attention layer's rows `(S, C,
  Hkv·Dh)`, which grow with the rung. `step` attends both through
  `flash_attention_decode(..., lengths=)` (a full ring is attended whole:
  a softmax does not care in which order rows come); `prefill` runs a
  sliding layer's prompt through the BANDED prefill kernel and grafts the
  prompt's last `window` real positions into their ring rows. Its expert
  layers count as NemotronHDecoder's do, and the attention counts the
  rows in use of each kind of layer.

- **Lfm2MoeDecoder** — stacks built on `models/lfm2_moe.py` params, whose
  layers are gated short convolutions or grouped-query attention and whose
  cache holds state of both kinds: a conv layer's TAIL `(S, K-1, H)`, the
  last K-1 rows of `B * x` that its convolution reads, which no rung
  touches, beside an attention layer's K and V rows `(S, C, Hkv·Dh)`,
  which grow with the rung. `prefill` grafts a tail as the last REAL
  positions of the padded prompt left it (`decoder_common.conv_tail`);
  `step` shifts it by the token (`decoder_common.conv_shift`) and attends
  through `flash_attention_decode(..., lengths=)`. Its expert layers count
  as NemotronHDecoder's do, and the attention counts its rows in use.

The contract (all pure functions, traced into AOT executables by the
server — nothing here may touch the host):

    model_args()                  -> tuple of non-donated leading args
    step(margs, cache, tokens, pos)            -> (logits (S,V), cache')
    prefill(margs, cache, slot, prompt, plen)  -> (cache', logits (V,))
    grow(cache, new_len)          -> cache padded to a longer rung
    init_cache(slots, cache_len)  -> donated cache pytree

State that has no rung. The cache is the decoder's own pytree and `grow`
its own function, so a decoder declares per leaf what a rung means: `grow`
pads the leaves that hold a row a cached position (K/V) and returns every
other leaf as it is (a recurrent state, a convolution tail, a counter);
`prefill` grafts one slot's part into both kinds. `uses_cache_rungs` says
only whether ANY leaf grows (False lets the server keep one rung). The
server never looks inside.

Device-side counters (optional). A decoder that counts on the device gives
their names in `counter_names` and keeps the running counts (int32,
wrapping) in its cache; `counters(cache)` hands them out as a
`(len(counter_names),)` vector. The server appends that vector to the token
block of every superstep, so the counts reach the host with the fetch the
loop makes anyway, and `status()` shows them cumulative.

The BertDecoder cache pytree: `{"k": [L leaves], "v": [L leaves]}`, each
leaf `(S, C, H·Dh)` in the compute dtype; `kv_dtype="int8"` stores the
leaves int8 and adds `"ks"`/`"vs"`, L leaves `(S, C, H)` of float32
per-(row, head) scales. The server treats it as opaque.

PAGED mode (`BertDecoder(..., page_size=ps, pool_pages=P)`): each leaf
becomes a pool `(P, ps, H·Dh)` (scales `(P, ps, H)`) — P fixed-size pages
shared by every slot, rows major like the dense leaf, so a slot's
gathered view `(S, C, H·Dh)` is a gather and a free reshape — and
`step`/`verify`/`prefill` take the per-slot
page index the host allocator (generation/paging.py) computes between
dispatches (`ptab` (S, rung//ps) for decode reads/writes, `wrow`
(ceil(P_bucket/ps),) write-redirect for prefill). Physical page 0 is the
null page: unmapped reads land there (hidden by the cache mask) and
redundant writes (shared-prefix re-prefill, frozen-lane rewrites past a
request's budget) are redirected into it. `grow` is the identity — the
pool is rung-independent; a rung only sets the gathered view width — and
`page_copy` is the copy-on-write primitive. Attention reads through
`flash_attention_decode_paged` / `_mq_paged`, whose gather feeds the
UNCHANGED masked-softmax arithmetic, so paged streams are bit-identical
to slot-contiguous ones.
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.kernels.flash_attention import (
    decode_tile_rows, flash_attention, flash_attention_decode,
    flash_attention_decode_mq, flash_attention_decode_mq_paged,
    flash_attention_decode_paged)
from deeplearning4j_tpu.kernels.indexer import (index_scores_decode,
                                                pack_rows, write_packed_row)
from deeplearning4j_tpu.kernels.mla_attention import (latent_tile_positions,
                                                      pack_latent)
from deeplearning4j_tpu.kernels.selection import (compact_indices,
                                                  top_k_mask)
from deeplearning4j_tpu.models import (afmoe, decoder_common, deepseek_v3,
                                       keye_vl, lfm2_moe, nemotron_h)
from deeplearning4j_tpu.models.bert import (_ffn, _layer_norm,
                                            bert_mlm_logits)
from deeplearning4j_tpu.parallel.ring_attention import dense_attention

__all__ = ["AfmoeDecoder", "BertDecoder", "KeyeDecoder", "Lfm2MoeDecoder",
           "MLADecoder", "NemotronHDecoder", "RecurrentDecoder"]


def _write_kv(cache, li, wi, wj, k, v):
    """Layer `li`'s K/V rows `k`/`v` (..., Hkv·Dh) into its leaves at
    (`wi`, `wj`) — whole rows, in place. `cache` is a dict of per-layer
    leaf LISTS."""
    kc, vc = cache["k"][li], cache["v"][li]
    cache["k"][li] = kc.at[wi, wj].set(k.astype(kc.dtype))
    cache["v"][li] = vc.at[wi, wj].set(v.astype(vc.dtype))


def _slot_index(pos):
    """Rows at per-slot positions `pos` ((S,) or an (S, d) block) of a
    slot-contiguous cache: the slot index to pair with `pos`."""
    return jnp.arange(pos.shape[0]).reshape((-1,) + (1,) * (pos.ndim - 1))


def _shape_tree_repr(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return repr((str(treedef),
                 tuple((tuple(l.shape), str(jnp.result_type(l)))
                       for l in leaves)))


def _expert_layer(params):
    """The first of a model's layers that routes to experts."""
    return next(layer for layer in params["layers"] if "router" in layer)


class BertDecoder:
    """KV-cache decode over a `models/bert.py` parameter tree.

    The full-sequence reference this must match (≤ 1e-5) is
    `bert_encode(..., causal=True)` + `bert_mlm_logits` over the same
    prompt+generated prefix."""

    uses_cache_rungs = True
    n_model_args = 1

    def __init__(self, cfg, params, attn_impl="auto", kv_dtype="fp",
                 page_size=None, pool_pages=None):
        if cfg.moe_layers:
            raise ValueError(
                "BertDecoder does not support MoE layers (dense-dispatch "
                "expert FFNs have no single-token decode path yet)")
        if attn_impl not in ("auto", "dense", "pallas"):
            raise ValueError(
                f"attn_impl must be 'auto', 'dense' or 'pallas', "
                f"got {attn_impl!r}")
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(
                f"kv_dtype must be 'fp' or 'int8', got {kv_dtype!r}")
        if kv_dtype == "int8" and attn_impl == "pallas":
            raise ValueError(
                "attn_impl='pallas' has no int8-cache variant — the "
                "quantized decode contraction runs the scale-folding "
                "einsum path; use attn_impl='auto' or 'dense' with "
                "kv_dtype='int8'")
        self.cfg = cfg
        self.params = params
        self.attn_impl = attn_impl
        # "int8": K/V rows stored int8 with per-(head, position) f32
        # scales (quantize/kvcache.py) and dequantized INSIDE
        # flash_attention_decode — the steady-state cache read (the
        # decode step's dominant traffic) drops to ~¼ width
        self.kv_dtype = kv_dtype
        self.vocab_size = int(cfg.vocab_size)
        self.max_cache_len = int(cfg.max_position_embeddings)
        # paged KV: pool_pages fixed-size pages of page_size rows each,
        # shared by all slots through a per-slot page index (page 0 is
        # the null page — see generation/paging.py for the layout
        # contract). pool_pages is the explicit HBM knob: a ragged
        # request costs ceil(len/ps) pages instead of a whole rung.
        self.paged = page_size is not None
        if self.paged:
            self.page_size = int(page_size)
            if self.page_size < 1:
                raise ValueError(
                    f"page_size must be >= 1, got {page_size}")
            if pool_pages is None:
                raise ValueError(
                    "paged mode needs an explicit pool_pages — the page "
                    "pool (not the rung) is the real HBM budget; "
                    "slots * rung // page_size + 1 reproduces the "
                    "slot-contiguous footprint")
            self.pool_pages = int(pool_pages)
            if self.pool_pages < 2:
                raise ValueError(
                    f"pool_pages must be >= 2 (null page + 1), "
                    f"got {pool_pages}")
        else:
            if pool_pages is not None:
                raise ValueError("pool_pages requires page_size")
            self.page_size = self.pool_pages = None

    def fingerprint(self):
        # the cache tree is part of every executable's signature: a
        # program stored for another cache layout must not be handed
        # this one's state
        parts = ("bert-decode", repr(self.cfg), self.attn_impl,
                 self.kv_dtype, self.page_size, self.pool_pages,
                 _shape_tree_repr(self.params),
                 _shape_tree_repr(
                     jax.eval_shape(lambda: self.init_cache(1, 1))))
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]

    def model_args(self):
        return (self.params,)

    def init_cache(self, slots, cache_len):
        cfg = self.cfg
        # pooled pages are slot- and rung-independent: the rung only
        # sets the gathered view width (ptab columns); HBM is
        # pool_pages × page_size rows, int8 halving page bytes
        lead = ((self.pool_pages, self.page_size) if self.paged
                else (slots, cache_len))

        def leaves(width, dtype, make=jnp.zeros):
            return [make(lead + (width,), dtype)
                    for _ in range(cfg.num_layers)]

        hidden = cfg.num_heads * cfg.head_dim
        if self.kv_dtype == "int8":
            return {"k": leaves(hidden, jnp.int8),
                    "v": leaves(hidden, jnp.int8),
                    "ks": leaves(cfg.num_heads, jnp.float32, jnp.ones),
                    "vs": leaves(cfg.num_heads, jnp.float32, jnp.ones)}
        return {"k": leaves(hidden, cfg.compute_dtype),
                "v": leaves(hidden, cfg.compute_dtype)}

    def grow(self, cache, new_len):
        if self.paged:      # the pool is rung-independent
            return cache

        def pad(t, fill):
            return jnp.pad(t, ((0, 0), (0, int(new_len) - t.shape[1]),
                               (0, 0)), constant_values=fill)

        # scale rows pad at 1 (zero rows round-trip)
        return {name: [pad(t, 1 if name in ("ks", "vs") else 0)
                       for t in leaves]
                for name, leaves in cache.items()}

    def page_copy(self, cache, src, dst):
        """Copy physical page `src` over `dst` in every layer's pool
        leaves — the copy-on-write primitive: the host allocator
        dispatches this (pre-compiled, donated) before the first block
        that would write into a shared page."""
        def copy(pool):
            page = lax.dynamic_slice(pool, (src, 0, 0),
                                     (1,) + pool.shape[1:])
            return lax.dynamic_update_slice(pool, page, (dst, 0, 0))

        return jax.tree_util.tree_map(copy, cache)

    def _embed(self, params, tokens, pos):
        """Token + position embedding at per-slot positions (mirrors
        bert_encode's embedding block; token_type unused in LM mode)."""
        emb = params["embeddings"]
        x = jnp.take(emb["word"], tokens, axis=0) \
            + jnp.take(emb["position"], pos, axis=0)
        return _layer_norm(x.astype(self.cfg.compute_dtype),
                           emb["ln_scale"], emb["ln_bias"],
                           self.cfg.layer_norm_eps)

    def _decode_attn(self, q, kc, vc, cmask, ptab=None, ks=None, vs=None,
                     lengths=None):
        """One layer's decode attention over its cache leaves — through
        the page index when `ptab` is given. `lengths` (S,): what `cmask`
        says as a count, rows 0..lengths - 1 of a slot in use; handed to
        the kernel over a dense float cache, which then reads a slot's
        rows in use and not its rung."""
        impl = self.attn_impl
        if impl == "auto":
            # int8 cache: the quantized decode GEMV reads the cache at
            # int8 width through the scale-folding einsum on every
            # backend (no Pallas int8-cache kernel yet; explicit
            # 'pallas' + int8 is rejected at construction)
            impl = ("pallas" if jax.default_backend() == "tpu"
                    and ks is None else "dense")
        if ptab is not None:
            return flash_attention_decode_paged(
                q, kc, vc, ptab, cmask, impl=impl, k_scale_pool=ks,
                v_scale_pool=vs)
        return flash_attention_decode(
            q, kc, vc, cmask, impl=impl, k_scale=ks, v_scale=vs,
            lengths=lengths if ks is None else None)

    def _prefill_attn(self, q, k, v):
        if self.attn_impl == "pallas" or (
                self.attn_impl == "auto"
                and jax.default_backend() == "tpu"):
            return flash_attention(q, k, v, causal=True)
        return dense_attention(q, k, v, causal=True)

    def _quantize_heads(self, rows):
        """(..., H·Dh) K or V rows -> (int8 rows (..., H·Dh), float32
        scales (..., H)): every head quantizes its Dh lanes of a row
        against that row's own absmax (quantize/kvcache.py)."""
        from deeplearning4j_tpu.quantize.kvcache import quantize_rows
        nh, hd = self.cfg.num_heads, self.cfg.head_dim
        q, scale = quantize_rows(rows.reshape(rows.shape[:-1] + (nh, hd)))
        return q.reshape(rows.shape), scale

    def _write_rows(self, cache, li, wi, wj, k, v):
        """Layer `li`'s new K/V rows `k`/`v` (..., H·Dh) into its leaves
        at (`wi`, `wj`) = (slot, position), or (page, offset) when paged
        — whole rows of H·Dh lanes, quantized first for an int8 cache.
        `cache` is a dict of per-layer leaf LISTS, updated in place."""
        if self.kv_dtype == "int8":
            k, k_sc = self._quantize_heads(k)
            v, v_sc = self._quantize_heads(v)
            cache["ks"][li] = cache["ks"][li].at[wi, wj].set(k_sc)
            cache["vs"][li] = cache["vs"][li].at[wi, wj].set(v_sc)
        _write_kv(cache, li, wi, wj, k, v)

    def _write_index(self, cache, pos, ptab):
        """Where rows at per-slot positions `pos` ((S,) or an (S, d)
        draft block) land: (`wi`, `wj`) = (slot, position), or (page,
        offset) through `ptab` when paged — and the row count C of the
        view attention reads. Paged writes past the mapped view (a
        frozen lane at pos == C) are redirected to the null page: a
        dense cache silently DROPS that out-of-range scatter; pages must
        redirect it explicitly or the clamped index would corrupt a
        live row."""
        ar = _slot_index(pos)
        if not self.paged:
            return ar, pos, cache["k"][0].shape[1]
        psz = self.page_size
        maxp = ptab.shape[1]
        c = maxp * psz
        phys = ptab[ar, jnp.minimum(pos // psz, maxp - 1)]
        return jnp.where(pos < c, phys, 0), pos % psz, c

    def step(self, margs, cache, tokens, pos, ptab=None):
        """One decode step for the whole batch: embed `tokens` at their
        slot positions, write each slot's K/V row at `pos`, attend the
        single query over rows 0..pos, and return next-token logits.
        `pos[s]` = number of already-cached tokens in slot s (the
        position the current token occupies). Paged mode additionally
        takes `ptab` (S, maxp) int32 — reads gather through it and the
        row write lands in page `pos // ps` at offset `pos % ps`
        (`_write_index`)."""
        (params,) = margs
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = self._embed(params, tokens, pos)        # (S, H)
        cache = {name: list(leaves) for name, leaves in cache.items()}
        int8_kv = self.kv_dtype == "int8"
        s = tokens.shape[0]
        nh, hd = cfg.num_heads, cfg.head_dim
        wi, wj, c = self._write_index(cache, pos, ptab)
        # rows 0..pos are valid (the current write included)
        cmask = jnp.arange(c)[None, :] <= pos[:, None]  # (S, C)
        in_use = jnp.minimum(pos + 1, c)                # (S,)
        dt = x.dtype
        # the stages of a layer are named scopes (compile-time metadata:
        # a profiler trace attributes the step's device time to them)
        for li, layer in enumerate(params["layers"]):
            with jax.named_scope(f"layer{li}"):
                with jax.named_scope("qkv"):
                    qkv = x @ layer["qkv_W"].astype(dt) \
                        + layer["qkv_b"].astype(dt)     # (S, 3H)
                    q, k, v = jnp.split(qkv, 3, axis=-1)
                with jax.named_scope("kv_write"):
                    self._write_rows(cache, li, wi, wj, k, v)
                with jax.named_scope("attn"):
                    ctx = self._decode_attn(
                        q.reshape(s, nh, hd), cache["k"][li],
                        cache["v"][li], cmask, ptab,
                        cache["ks"][li] if int8_kv else None,
                        cache["vs"][li] if int8_kv else None, in_use)
                    ctx = ctx.astype(dt)
                with jax.named_scope("proj"):
                    a = ctx.reshape(s, cfg.hidden_size) \
                        @ layer["proj_W"].astype(dt) \
                        + layer["proj_b"].astype(dt)
                    x = _layer_norm(x + a, layer["ln1_scale"],
                                    layer["ln1_bias"], cfg.layer_norm_eps)
                with jax.named_scope("ffn"):
                    f = _ffn(cfg, layer, x, False, None)
                    x = _layer_norm(x + f, layer["ln2_scale"],
                                    layer["ln2_bias"], cfg.layer_norm_eps)
        with jax.named_scope("logits"):
            logits = bert_mlm_logits(cfg, params, x[:, None, :])[:, 0]
        return logits, cache

    @property
    def supports_draft(self):
        """Greedy drafting needs the multi-token `verify` forward; the
        int8 KV codec has no multi-row quantized write path yet, so
        drafting is fp-cache only."""
        return self.kv_dtype == "fp"

    def verify(self, margs, cache, tokens, pos, draft, ptab=None):
        """Draft-block decode: for each slot, run the q-block
        ``[tokens[s], draft[s, 0], ..., draft[s, d-2]]`` at positions
        ``pos[s] .. pos[s]+d-1`` through the stack in ONE dispatch —
        write all d K/V rows, attend each query over cache rows
        ``0 .. pos[s]+j`` (the intra-block causal offset), and return
        logits at every query: ``logits[s, j]`` is the model's
        next-token distribution after consuming j draft tokens.
        Exactly equal (same arithmetic, same masks) to d sequential
        `step` calls — the greedy-drafting acceptance rule's oracle.
        Rows written past the accepted prefix hold draft garbage but
        sit beyond the slot's advanced position, so the decode cache
        mask hides them until they are overwritten (same convention as
        prefill's padded rows). fp cache only (`supports_draft`)."""
        (params,) = margs
        cfg = self.cfg
        s = tokens.shape[0]
        d = 1 + draft.shape[1]
        tok_block = jnp.concatenate([tokens[:, None], draft], axis=1)
        pos_block = pos[:, None] + jnp.arange(d)[None, :]   # (S, d)
        with jax.named_scope("embed"):
            x = self._embed(params, tok_block, pos_block)   # (S, d, H)
        cache = {name: list(leaves) for name, leaves in cache.items()}
        nh, hd = cfg.num_heads, cfg.head_dim
        # rows pos..pos+d-1 of every slot
        wi, wj, c = self._write_index(cache, pos_block, ptab)
        # query j sees rows 0..pos+j (its own write included)
        qmask = jnp.arange(c)[None, None, :] <= pos_block[:, :, None]
        dt = x.dtype
        for li, layer in enumerate(params["layers"]):
            with jax.named_scope(f"layer{li}"):
                with jax.named_scope("qkv"):
                    qkv = x @ layer["qkv_W"].astype(dt) \
                        + layer["qkv_b"].astype(dt)         # (S, d, 3H)
                    q, k, v = jnp.split(qkv, 3, axis=-1)
                    q = q.reshape(s, d, nh, hd).transpose(0, 2, 1, 3)
                with jax.named_scope("kv_write"):
                    self._write_rows(cache, li, wi, wj, k, v)
                with jax.named_scope("attn"):
                    kc, vc = cache["k"][li], cache["v"][li]
                    if self.paged:
                        ctx = flash_attention_decode_mq_paged(
                            q, kc, vc, ptab, qmask)
                    else:
                        ctx = flash_attention_decode_mq(q, kc, vc, qmask)
                    ctx = ctx.astype(dt)
                with jax.named_scope("proj"):
                    a = ctx.transpose(0, 2, 1, 3).reshape(
                        s, d, cfg.hidden_size) \
                        @ layer["proj_W"].astype(dt) \
                        + layer["proj_b"].astype(dt)
                    x = _layer_norm(x + a, layer["ln1_scale"],
                                    layer["ln1_bias"], cfg.layer_norm_eps)
                with jax.named_scope("ffn"):
                    f = _ffn(cfg, layer, x, False, None)
                    x = _layer_norm(x + f, layer["ln2_scale"],
                                    layer["ln2_bias"], cfg.layer_norm_eps)
        with jax.named_scope("logits"):
            logits = bert_mlm_logits(cfg, params, x)        # (S, d, V)
        return logits, cache

    def _write_prompt_pages(self, pool, block, wrow):
        """Scatter a prefill K/V (or scale) block into pool pages:
        `pool` is one layer's (P, ps, W) pool leaf, `block` the prompt's
        (P_bucket, W) rows, `wrow[j]` the physical page logical page j
        writes into — 0 (the null page) for pages whose bytes already
        exist on device (shared-prefix hit) or that hold only bucket
        padding, so redundant writes are discarded without branching."""
        psz = self.page_size
        npp = wrow.shape[0]
        pages = jnp.pad(
            block, ((0, npp * psz - block.shape[0]), (0, 0))
        ).reshape(npp, psz, block.shape[1]).astype(pool.dtype)
        for j in range(npp):
            pool = lax.dynamic_update_slice(pool, pages[j][None],
                                            (wrow[j], 0, 0))
        return pool

    def prefill(self, margs, cache, slot, prompt, plen, wrow=None):
        """Causal full forward over one length-bucketed prompt (1, P);
        writes the slot's K/V block for rows 0..P-1 in one shot and
        returns the logits at the last REAL position (plen - 1). Rows
        beyond plen hold padding garbage — masked out by the decode
        cache mask (pos starts at plen), so a bucketed prompt serves
        bit-the-same as an exact-length one. Paged mode writes through
        the `wrow` redirect instead of the slot's rows (see
        `_write_prompt_pages`); the forward itself is identical, so a
        shared-prefix admission still yields exact first-token
        logits."""
        (params,) = margs
        cfg = self.cfg
        p_len = prompt.shape[0]
        emb = params["embeddings"]
        with jax.named_scope("embed"):
            x = jnp.take(emb["word"], prompt[None], axis=0) \
                + emb["position"][None, :p_len]
            x = _layer_norm(x.astype(cfg.compute_dtype), emb["ln_scale"],
                            emb["ln_bias"], cfg.layer_norm_eps)
        cache = {name: list(leaves) for name, leaves in cache.items()}
        int8_kv = self.kv_dtype == "int8"
        nh, hd = cfg.num_heads, cfg.head_dim
        dt = x.dtype

        def heads(t):       # the prefill attention's view only
            return t.reshape(1, p_len, nh, hd).transpose(0, 2, 1, 3)

        def write(name, li, block):
            """The prompt's (P, W) block into the slot's rows of leaf
            `name`, as it is — or through the page redirect."""
            leaf = cache[name][li]
            if self.paged:
                cache[name][li] = self._write_prompt_pages(leaf, block,
                                                           wrow)
            else:
                cache[name][li] = lax.dynamic_update_slice(
                    leaf, block[None].astype(leaf.dtype), (slot, 0, 0))

        for li, layer in enumerate(params["layers"]):
            with jax.named_scope(f"layer{li}"):
                with jax.named_scope("qkv"):
                    qkv = x @ layer["qkv_W"].astype(dt) \
                        + layer["qkv_b"].astype(dt)     # (1, P, 3H)
                    q, k, v = jnp.split(qkv, 3, axis=-1)
                with jax.named_scope("kv_write"):
                    kw, vw = k[0], v[0]                 # (P, H·Dh)
                    if int8_kv:
                        kw, k_sc = self._quantize_heads(kw)
                        vw, v_sc = self._quantize_heads(vw)
                        write("ks", li, k_sc)
                        write("vs", li, v_sc)
                    write("k", li, kw)
                    write("v", li, vw)
                with jax.named_scope("attn"):
                    ctx = self._prefill_attn(heads(q), heads(k), heads(v))
                with jax.named_scope("proj"):
                    a = ctx.transpose(0, 2, 1, 3).reshape(
                        1, p_len, cfg.hidden_size) \
                        @ layer["proj_W"].astype(dt) \
                        + layer["proj_b"].astype(dt)
                    x = _layer_norm(x + a, layer["ln1_scale"],
                                    layer["ln1_bias"], cfg.layer_norm_eps)
                with jax.named_scope("ffn"):
                    f = _ffn(cfg, layer, x, False, None)
                    x = _layer_norm(x + f, layer["ln2_scale"],
                                    layer["ln2_bias"], cfg.layer_norm_eps)
        with jax.named_scope("logits"):
            h_last = jnp.take(x[0], plen - 1, axis=0)   # (H,)
            logits = bert_mlm_logits(cfg, params,
                                     h_last[None, None, :])[0, 0]
        return cache, logits


class NemotronHDecoder:
    """Decode over a `models/nemotron_h.py` parameter tree: K/V leaves for
    the attention layers beside Mamba-2 state and convolution-tail leaves,
    in one donated cache.

    The cache pytree: `{"k": [...], "v": [...]}`, one `(S, C, Hkv·Dh)`
    leaf an ATTENTION layer in the compute dtype; `{"ssm": [...], "conv":
    [...]}`, one `(S, heads, head_dim, state)` float32 leaf and one `(S,
    K-1, conv lanes)` leaf a MAMBA layer; `"counts"`, the expert layers'
    running counts (`counter_names`). Only the K/V leaves know the rung.

    The full-sequence reference this must match is `nemotron_h.forward`
    over the same prompt+generated prefix (and, outside the package, the
    plain reference under `benchmarks/families/`)."""

    uses_cache_rungs = True
    n_model_args = 1
    supports_draft = False      # the model's prediction module is not run
    max_cache_len = None        # no position table bounds the length
    counter_names = ("moe_pairs", "moe_expert_reads", "moe_pairs_max")

    def __init__(self, cfg, params, attn_impl="auto"):
        if attn_impl not in ("auto", "dense", "pallas"):
            raise ValueError(
                f"attn_impl must be 'auto', 'dense' or 'pallas', "
                f"got {attn_impl!r}")
        self.cfg = cfg
        self.params = params
        self.attn_impl = attn_impl
        self.vocab_size = int(cfg.vocab_size)
        # layer index -> index among the layers of its kind (its leaves)
        self._n, self._leaf = {}, []
        for kind in cfg.pattern:
            self._leaf.append(self._n.get(kind, 0))
            self._n[kind] = self._leaf[-1] + 1

    def fingerprint(self):
        parts = ("nemotron-h-decode", repr(self.cfg), self.attn_impl,
                 _shape_tree_repr(self.params),
                 _shape_tree_repr(
                     jax.eval_shape(lambda: self.init_cache(1, 1))))
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]

    def expert_runs(self, bucket):
        """How many times one expert layer of an admit of `bucket` tokens
        reads the layer's experts (`nemotron_h.expert_runs`)."""
        return nemotron_h.expert_runs(self.cfg, _expert_layer(self.params),
                                      bucket)

    def model_args(self):
        return (self.params,)

    def init_cache(self, slots, cache_len):
        cfg = self.cfg
        dt = cfg.compute_dtype
        na, nm = self._n.get("*", 0), self._n.get("M", 0)

        def leaves(n, shape, dtype):
            return [jnp.zeros((slots,) + shape, dtype) for _ in range(n)]

        return {"k": leaves(na, (cache_len, cfg.kv_width), dt),
                "v": leaves(na, (cache_len, cfg.kv_width), dt),
                "ssm": leaves(nm, (cfg.mamba_num_heads, cfg.mamba_head_dim,
                                   cfg.ssm_state_size), jnp.float32),
                "conv": leaves(nm, (cfg.conv_kernel - 1, cfg.conv_dim), dt),
                "counts": jnp.zeros((len(self.counter_names),), jnp.int32)}

    def grow(self, cache, new_len):
        """The K/V leaves padded to the longer rung; the state leaves, the
        tails and the counts as they are."""
        def pad(t):
            return jnp.pad(t, ((0, 0), (0, int(new_len) - t.shape[1]),
                               (0, 0)))
        return {**cache, "k": [pad(t) for t in cache["k"]],
                "v": [pad(t) for t in cache["v"]]}

    def counters(self, cache):
        return cache["counts"]

    def step(self, margs, cache, tokens, pos):
        """One decode step for the whole batch: each attention layer
        writes its K/V row at `pos` and attends rows 0..pos; each Mamba-2
        layer advances its slots' state and tail by the one token; each
        expert layer routes the S tokens and adds what it computed to the
        counts. Returns next-token logits (S, V)."""
        (params,) = margs
        cfg = self.cfg
        s = tokens.shape[0]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0)       # (S, H)
        cache = {name: list(v) if isinstance(v, (list, tuple)) else v
                 for name, v in cache.items()}
        wi = _slot_index(pos)
        if cache["k"]:
            # rows 0..pos are valid (the current write included)
            cmask = jnp.arange(cache["k"][0].shape[1])[None, :] \
                <= pos[:, None]
        for li, (kind, layer) in enumerate(zip(cfg.pattern,
                                               params["layers"])):
            i = self._leaf[li]
            with jax.named_scope(f"layer{li}"):
                with jax.named_scope("norm"):
                    u = nemotron_h.rms_norm(x, layer["norm"], cfg.norm_eps)
                if kind == "M":
                    with jax.named_scope("ssm"):
                        out, cache["ssm"][i], cache["conv"][i] = \
                            nemotron_h.mamba_step(cfg, layer, u,
                                                  cache["ssm"][i],
                                                  cache["conv"][i])
                elif kind == "*":
                    with jax.named_scope("attn"):
                        with jax.named_scope("qkv"):
                            q, k, v = nemotron_h.attention_qkv(cfg, layer,
                                                               u)
                        with jax.named_scope("kv_write"):
                            _write_kv(cache, i, wi, pos, k, v)
                        ctx = flash_attention_decode(
                            q.reshape(s, cfg.num_attention_heads,
                                      cfg.head_dim),
                            cache["k"][i], cache["v"][i], cmask,
                            impl=self.attn_impl)
                        with jax.named_scope("proj"):
                            out = ctx.reshape(s, -1).astype(x.dtype) \
                                @ layer["o"].astype(x.dtype)
                else:
                    with jax.named_scope("moe"):
                        out, counted = nemotron_h.moe_mixer(cfg, layer, u)
                        cache["counts"] = cache["counts"] + counted
                x = x + out.astype(x.dtype)
        return nemotron_h.logits(cfg, params, x), cache

    def prefill(self, margs, cache, slot, prompt, plen):
        """The full forward over one length-bucketed prompt (1, P), then
        the graft of ONE slot's part into both kinds of state: the K/V
        block for rows 0..P-1 (rows beyond plen hold padding garbage that
        the decode mask hides, as in BertDecoder), and the Mamba-2 state
        and tail as the last REAL token left them (`mamba_mixer` holds the
        state through the padding). Returns the logits at plen - 1."""
        (params,) = margs
        cfg = self.cfg
        x, states = nemotron_h.encode(cfg, params, prompt[None],
                                      jnp.reshape(plen, (1,)))
        cache = {name: list(v) if isinstance(v, (list, tuple)) else v
                 for name, v in cache.items()}

        def graft(name, i, part):
            leaf = cache[name][i]
            cache[name][i] = lax.dynamic_update_slice(
                leaf, part.astype(leaf.dtype),
                (slot,) + (0,) * (leaf.ndim - 1))

        for li, (kind, state) in enumerate(zip(cfg.pattern, states)):
            i = self._leaf[li]
            with jax.named_scope(f"layer{li}"):
                if kind == "*":
                    with jax.named_scope("attn"), \
                            jax.named_scope("kv_write"):
                        graft("k", i, state[0])
                        graft("v", i, state[1])
                elif kind == "M":
                    with jax.named_scope("ssm"), \
                            jax.named_scope("state_write"):
                        graft("ssm", i, state[0])
                        graft("conv", i, state[1])
        h_last = jnp.take(x[0], plen - 1, axis=0)               # (H,)
        return cache, nemotron_h.logits(cfg, params, h_last)


#: `KeyeDecoder.step` attends its kept rows where they lie while the rung
#: holds at most this many times `topk` rows, and gathers them past it.
#: Two rates measured on a v5e (`PERF.md`, PR 36; K and V rows of 1 KB):
#: gathered, a kept row costs 38.9 ns (XLA's row gather 2 x 15.2 ns,
#: the index arithmetic around it 5.8 and the kernel over the gathered
#: rung 2.7), whatever the context; read in place, a row in use costs 2.9
#: ns. In place wins while a slot has fewer than 38.9 / 2.9 = 13.4 x
#: `topk` rows in use, which the rung bounds: 12 leaves a tenth of room
_IN_PLACE_RUNGS = 12


def _attends_in_place(rung, topk):
    """Whether a sparse decode step over a rung of `rung` rows reads K and
    V in place under the selection mask (cost: the rows in use, which the
    rung bounds) or gathers the `topk` kept rows first (cost: 2 x `topk`
    row copies a slot, whatever the context). Both are one softmax over
    the same rows; the rung and `topk` are static shapes of the program
    being compiled, so the program holds one of them."""
    return rung <= _IN_PLACE_RUNGS * topk


class KeyeDecoder:
    """Decode over a `models/keye_vl.py` parameter tree: grouped-query
    attention over the cache rows a learned indexer selects.

    The cache pytree: `{"k": [...], "v": [...], "ki": [...]}`, one `(S, C,
    Hkv·Dh)` K leaf, one V leaf and one `(S, C / 2, 2·Di)` index-key leaf
    (positions 2r and 2r + 1 side by side in row r: `indexer.pack_rows`)
    A LAYER, in the compute dtype, rows major; `"counts"`, the running
    counts (`counter_names`). All three kinds of leaf know the rung, which
    is an even number of rows.

    The full-sequence reference this must match is `keye_vl.forward` over
    the same prompt+generated prefix (and, outside the package, the plain
    reference under `benchmarks/families/`)."""

    uses_cache_rungs = True
    n_model_args = 1
    supports_draft = False
    max_cache_len = None        # rotary positions: no table bounds them
    counter_names = ("moe_pairs", "moe_expert_reads", "moe_pairs_max",
                     "dsa_rows_scored", "dsa_rows_selected",
                     "dsa_rows_read")
    _LEAVES = ("k", "v", "ki")

    def __init__(self, cfg, params, attn_impl="auto"):
        if attn_impl not in ("auto", "dense", "pallas"):
            raise ValueError(
                f"attn_impl must be 'auto', 'dense' or 'pallas', "
                f"got {attn_impl!r}")
        self.cfg = cfg
        self.params = params
        self.attn_impl = attn_impl
        self.vocab_size = int(cfg.vocab_size)

    def fingerprint(self):
        parts = ("keye-decode", repr(self.cfg), self.attn_impl,
                 _shape_tree_repr(self.params),
                 _shape_tree_repr(
                     jax.eval_shape(lambda: self.init_cache(1, 2))))
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]

    def expert_runs(self, bucket):
        """How many times one expert layer of an admit of `bucket` tokens
        reads the layer's experts (`keye_vl.expert_runs`)."""
        return keye_vl.expert_runs(self.cfg, _expert_layer(self.params),
                                   bucket)

    def model_args(self):
        return (self.params,)

    def init_cache(self, slots, cache_len):
        cfg = self.cfg
        if cache_len % 2:
            raise ValueError(f"the index-key leaf packs two positions a "
                             f"row: cache rung {cache_len} must be even")
        shapes = {"k": (slots, cache_len, cfg.kv_width),
                  "v": (slots, cache_len, cfg.kv_width),
                  "ki": (slots, cache_len // 2, 2 * cfg.indexer_head_dim)}
        cache = {name: [jnp.zeros(shapes[name], cfg.compute_dtype)
                        for _ in range(cfg.num_hidden_layers)]
                 for name in self._LEAVES}
        cache["counts"] = jnp.zeros((len(self.counter_names),), jnp.int32)
        return cache

    def grow(self, cache, new_len):
        """All three kinds of leaf padded to the longer rung, together;
        the counts as they are."""
        def pad(t, rows):
            return jnp.pad(t, ((0, 0), (0, rows - t.shape[1]), (0, 0)))
        new_len = int(new_len)
        return {**cache, **{
            name: [pad(t, new_len // 2 if name == "ki" else new_len)
                   for t in cache[name]] for name in self._LEAVES}}

    def counters(self, cache):
        return cache["counts"]

    def step(self, margs, cache, tokens, pos):
        """One decode step for the whole batch. A layer writes its K, V
        and index-key rows at `pos`, scores every row in use against the
        slot's index query, keeps the `min(topk, pos + 1)` best and attends
        over THOSE rows, read in place under the selection mask or
        gathered into a `(S, topk, Hkv·Dh)` rung (`_attends_in_place`);
        then its expert layer routes the S tokens. Returns next-token
        logits (S, V). Text positions: all three rotary streams are the
        token's index. Counted a step, slots and layers summed: the rows
        scored, the rows kept, and the rows of K the attention kernel
        fetched for them (in place: a slot's rows in use rounded up to the
        kernel's tile)."""
        (params,) = margs
        cfg = self.cfg
        s = tokens.shape[0]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0)       # (S, H)
        cache = {name: list(v) if isinstance(v, (list, tuple)) else v
                 for name, v in cache.items()}
        wi = _slot_index(pos)
        c = cache["k"][0].shape[1]
        width = min(cfg.indexer_topk, c)
        # rows 0..pos are in use (the current write included); a lane
        # frozen at the end of its rung (pos == C) keeps all of them
        in_use = jnp.minimum(pos + 1, c)                        # (S,)
        keep = jnp.minimum(in_use, width)
        in_place = _attends_in_place(c, cfg.indexer_topk)
        if in_place:
            tile = decode_tile_rows(c, cfg.kv_width, cache["k"][0].dtype)
            read = (-(-in_use // tile) * tile).sum()
        else:
            kept = jnp.arange(width)[None, :] < keep[:, None]   # (S, topk)
            read = keep.sum()
        with jax.named_scope("rope"):
            tables = keye_vl.rope_tables(
                cfg, jnp.broadcast_to(pos[None], (3, s)))
        counted = jnp.zeros((3,), jnp.int32)
        for li, layer in enumerate(params["layers"]):
            with jax.named_scope(f"layer{li}"):
                u = keye_vl.rms_norm(x, layer["norm1"], cfg.rms_norm_eps)
                with jax.named_scope("attn"):
                    q, k, v, qi, ki, w = keye_vl.attention_inputs(
                        cfg, layer, u, tables)
                    with jax.named_scope("kv_write"):
                        _write_kv(cache, li, wi, pos, k, v)
                        cache["ki"][li] = write_packed_row(
                            cache["ki"][li], pos, ki)
                    with jax.named_scope("indexer"):
                        with jax.named_scope("score"):
                            scores = index_scores_decode(
                                qi, cache["ki"][li], w, pos,
                                cfg.index_scale, impl=self.attn_impl)
                        with jax.named_scope("select"):
                            mask = top_k_mask(scores, keep)
                            if not in_place:
                                rows = compact_indices(mask, width)
                    q = q.reshape(s, cfg.num_attention_heads, cfg.head_dim)
                    if in_place:
                        ctx = flash_attention_decode(
                            q, cache["k"][li], cache["v"][li], mask,
                            impl=self.attn_impl, lengths=in_use)
                    else:
                        with jax.named_scope("gather"):
                            kg, vg = (jnp.take_along_axis(
                                cache[name][li], rows[..., None], axis=1)
                                for name in ("k", "v"))
                        ctx = flash_attention_decode(q, kg, vg, kept,
                                                     impl=self.attn_impl)
                    with jax.named_scope("proj"):
                        x = x + ctx.reshape(s, -1).astype(x.dtype) \
                            @ layer["o"].astype(x.dtype)
                with jax.named_scope("moe"):
                    out, counts = keye_vl.moe(cfg, layer, keye_vl.rms_norm(
                        x, layer["norm2"], cfg.rms_norm_eps))
                    counted = counted + counts
                x = x + out.astype(x.dtype)
        layers = cfg.num_hidden_layers
        cache["counts"] = cache["counts"] + jnp.concatenate([
            counted, layers * jnp.stack(
                [in_use.sum(), keep.sum(), read]).astype(jnp.int32)])
        return keye_vl.logits(cfg, params, x), cache

    def prefill(self, margs, cache, slot, prompt, plen):
        """The full forward over one length-bucketed prompt (P,) with the
        indexer's selection at every position, then the graft of the
        slot's three blocks a layer for rows 0..P-1 (rows beyond plen hold
        padding garbage that the decode step never scores: it masks rows
        past `pos`). Returns the logits at plen - 1."""
        (params,) = margs
        cfg = self.cfg
        x, states = keye_vl.encode(cfg, params, prompt,
                                   impl=self.attn_impl)
        cache = {name: list(v) if isinstance(v, (list, tuple)) else v
                 for name, v in cache.items()}
        for li, state in enumerate(states):
            with jax.named_scope(f"layer{li}"), jax.named_scope("attn"), \
                    jax.named_scope("kv_write"):
                k, v, ki = state
                for name, part in (("k", k), ("v", v),
                                   ("ki", pack_rows(ki))):
                    leaf = cache[name][li]
                    cache[name][li] = lax.dynamic_update_slice(
                        leaf, part[None].astype(leaf.dtype), (slot, 0, 0))
        h_last = jnp.take(x, plen - 1, axis=0)                  # (H,)
        return cache, keye_vl.logits(cfg, params, h_last)


class MLADecoder:
    """Decode over a `models/deepseek_v3.py` parameter tree: multi-head
    latent attention over one cached row a position.

    The cache pytree: `{"kv": [...]}`, one `(S, C / 2, 2·(L + R))` leaf A
    LAYER in the compute dtype, rows major, row r holding positions 2r and
    2r + 1 as `[c, c', kr, kr']` (`mla_attention.pack_latent`); `"counts"`,
    the running counts (`counter_names`). The rung is an even number of
    rows.

    The full-sequence reference this must match is `deeplearning4j_tpu.
    models.deepseek_v3.forward` over the same prompt+generated prefix
    (and, outside the package, the plain reference under
    `benchmarks/families/`)."""

    uses_cache_rungs = True
    n_model_args = 1
    supports_draft = False      # the model's prediction module is not run
    max_cache_len = None        # rotary positions: no table bounds them
    counter_names = ("moe_pairs", "moe_expert_reads", "moe_pairs_max",
                     "mla_rows_attended", "mla_rows_read")

    def __init__(self, cfg, params, attn_impl="auto"):
        if attn_impl not in ("auto", "dense", "pallas"):
            raise ValueError(
                f"attn_impl must be 'auto', 'dense' or 'pallas', "
                f"got {attn_impl!r}")
        self.cfg = cfg
        self.params = params
        self.attn_impl = attn_impl
        self.vocab_size = int(cfg.vocab_size)

    def fingerprint(self):
        parts = ("mla-decode", repr(self.cfg), self.attn_impl,
                 _shape_tree_repr(self.params),
                 _shape_tree_repr(
                     jax.eval_shape(lambda: self.init_cache(1, 2))))
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]

    def expert_runs(self, bucket):
        """How many times one expert layer of an admit of `bucket` tokens
        reads the layer's experts (`decoder_common.expert_runs`)."""
        return decoder_common.expert_runs(self.cfg,
                                          _expert_layer(self.params), bucket)

    def prefill_attn_pairs(self, plen, bucket):
        """(run, static) grid steps of one head of the prefill kernel over
        an admit of `plen` tokens in `bucket`, every layer's causal call
        summed (`decoder_common.prefill_attn_pairs`); None off the
        kernel."""
        return decoder_common.prefill_attn_pairs(
            self.attn_impl, plen, bucket, deepseek_v3.PREFILL_BLOCK,
            [None] * self.cfg.num_hidden_layers)

    def model_args(self):
        return (self.params,)

    def init_cache(self, slots, cache_len):
        cfg = self.cfg
        if cache_len % 2:
            raise ValueError(f"the latent leaf packs two positions a row: "
                             f"cache rung {cache_len} must be even")
        return {"kv": [jnp.zeros((slots, cache_len // 2,
                                  2 * cfg.latent_width), cfg.compute_dtype)
                       for _ in range(cfg.num_hidden_layers)],
                "counts": jnp.zeros((len(self.counter_names),), jnp.int32)}

    def grow(self, cache, new_len):
        """Every latent leaf padded to the longer rung; the counts as they
        are."""
        def pad(t):
            return jnp.pad(t, ((0, 0), (0, int(new_len) // 2 - t.shape[1]),
                               (0, 0)))
        return {**cache, "kv": [pad(t) for t in cache["kv"]]}

    def counters(self, cache):
        return cache["counts"]

    def step(self, margs, cache, tokens, pos):
        """One decode step for the whole batch, in the absorbed form: a
        layer writes its latent row at `pos` and attends rows 0..pos of its
        leaf, each read once as key and as value; then its feed-forward,
        dense or experts. Returns next-token logits (S, V). Counted a step,
        slots and layers summed: the rows attended (what the mathematics
        needs) and the rows the kernel fetched for them (a slot's rows in
        use rounded up to its tile)."""
        (params,) = margs
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0)       # (S, H)
        leaves = list(cache["kv"])
        c = 2 * leaves[0].shape[1]
        # rows 0..pos are in use (the current write included); a lane
        # frozen at the end of its rung (pos == C) keeps all of them
        in_use = jnp.minimum(pos + 1, c)                        # (S,)
        tile = latent_tile_positions(c, cfg.kv_lora_rank, leaves[0].dtype)
        read = (-(-in_use // tile) * tile).sum()
        with jax.named_scope("rope"):
            tables = deepseek_v3.rope_tables(cfg, pos)
        counted = jnp.zeros((3,), jnp.int32)
        for li, layer in enumerate(params["layers"]):
            with jax.named_scope(f"layer{li}"):
                x, leaves[li], counts = deepseek_v3.decode_layer(
                    cfg, li, layer, x, tables, leaves[li], pos, in_use,
                    self.attn_impl)
                if counts is not None:
                    counted = counted + counts
        counts = cache["counts"] + jnp.concatenate([
            counted, cfg.num_hidden_layers * jnp.stack(
                [in_use.sum(), read]).astype(jnp.int32)])
        return deepseek_v3.logits(cfg, params, x), \
            {"kv": leaves, "counts": counts}

    def prefill(self, margs, cache, slot, prompt, plen):
        """The full forward over one length-bucketed prompt (P,) in the
        expanded form, its attention over the tile pairs up to plen alone
        (`deepseek_v3.causal_attention`), then the graft of the slot's
        latent rows 0..P-1 a
        layer (rows beyond plen hold padding garbage that the decode step
        never reads: it attends rows up to `pos`). Returns the logits at
        plen - 1."""
        (params,) = margs
        cfg = self.cfg
        x, states = deepseek_v3.encode(cfg, params, prompt,
                                       impl=self.attn_impl, plen=plen)
        leaves = list(cache["kv"])
        for li, (c, kr) in enumerate(states):
            with jax.named_scope(f"layer{li}"), jax.named_scope("attn"), \
                    jax.named_scope("kv_write"):
                leaves[li] = lax.dynamic_update_slice(
                    leaves[li],
                    pack_latent(c, kr)[None].astype(leaves[li].dtype),
                    (slot, 0, 0))
        h_last = jnp.take(x, plen - 1, axis=0)                  # (H,)
        return {**cache, "kv": leaves}, \
            deepseek_v3.logits(cfg, params, h_last)


class AfmoeDecoder:
    """Decode over a `models/afmoe.py` parameter tree: sliding-window and
    full grouped-query attention layers, each with the cache its kind
    needs.

    The cache pytree: `{"k": [...], "v": [...]}`, one K and one V leaf A
    LAYER in the compute dtype, rows major: a sliding layer's `(S, min(W,
    C), Hkv·Dh)` ring (W the window; position p at row p mod W), a full
    layer's `(S, C, Hkv·Dh)`; `"counts"`, the running counts
    (`counter_names`). Only the full layers' leaves, and a ring shorter
    than W, know the rung.

    The full-sequence reference this must match is `deeplearning4j_tpu.
    models.afmoe.forward` over the same prompt+generated prefix (and,
    outside the package, the plain reference under `benchmarks/families/`)."""

    uses_cache_rungs = True
    n_model_args = 1
    supports_draft = False
    max_cache_len = None        # rotary positions: no table bounds them
    counter_names = ("moe_pairs", "moe_expert_reads", "moe_pairs_max",
                     "window_rows_attended", "global_rows_attended")

    def __init__(self, cfg, params, attn_impl="auto"):
        if attn_impl not in ("auto", "dense", "pallas"):
            raise ValueError(
                f"attn_impl must be 'auto', 'dense' or 'pallas', "
                f"got {attn_impl!r}")
        self.cfg = cfg
        self.params = params
        self.attn_impl = attn_impl
        self.vocab_size = int(cfg.vocab_size)

    def fingerprint(self):
        parts = ("afmoe-decode", repr(self.cfg), self.attn_impl,
                 _shape_tree_repr(self.params),
                 _shape_tree_repr(
                     jax.eval_shape(lambda: self.init_cache(1, 1))))
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]

    def expert_runs(self, bucket):
        """How many times one expert layer of an admit of `bucket` tokens
        reads the layer's experts (`decoder_common.expert_runs`)."""
        return decoder_common.expert_runs(self.cfg,
                                          _expert_layer(self.params), bucket)

    def prefill_attn_pairs(self, plen, bucket):
        """(run, static) grid steps of one head of the prefill kernels over
        an admit of `plen` tokens in `bucket`, the sliding layers' bands
        and the full layers' triangles summed
        (`decoder_common.prefill_attn_pairs`); None off the kernel."""
        cfg = self.cfg
        return decoder_common.prefill_attn_pairs(
            self.attn_impl, plen, bucket, afmoe.PREFILL_BLOCK,
            [cfg.sliding_window if cfg.is_sliding(li) else None
             for li in range(cfg.num_hidden_layers)])

    def model_args(self):
        return (self.params,)

    def _rows(self, li, cache_len):
        """Rows of layer `li`'s leaves at rung `cache_len`."""
        if self.cfg.is_sliding(li):
            return min(self.cfg.sliding_window, int(cache_len))
        return int(cache_len)

    def init_cache(self, slots, cache_len):
        cfg = self.cfg
        cache = {name: [jnp.zeros((slots, self._rows(li, cache_len),
                                   cfg.kv_width), cfg.compute_dtype)
                        for li in range(cfg.num_hidden_layers)]
                 for name in ("k", "v")}
        cache["counts"] = jnp.zeros((len(self.counter_names),), jnp.int32)
        return cache

    def grow(self, cache, new_len):
        """A full layer's leaves padded to the longer rung, a ring up to
        the window's rows while it is shorter (it held positions 0..C - 1
        at their own rows, none wrapped); the counts as they are."""
        def pad(li, t):
            return jnp.pad(t, ((0, 0), (0, self._rows(li, new_len)
                                        - t.shape[1]), (0, 0)))
        return {**cache, **{name: [pad(li, t)
                                   for li, t in enumerate(cache[name])]
                            for name in ("k", "v")}}

    def counters(self, cache):
        return cache["counts"]

    def step(self, margs, cache, tokens, pos):
        """One decode step for the whole batch: a layer writes its K and V
        rows at `pos` (a full layer) or `pos mod W` (a sliding layer's
        ring) and attends the rows in use, `min(pos + 1, rows)`: on a full
        ring that is every row, the last W positions; then its
        feed-forward, dense or experts. Returns next-token logits (S, V).
        Counted a step, slots and layers summed: the rows in use of the
        sliding layers and of the full layers."""
        (params,) = margs
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = afmoe.embed(cfg, params, tokens)                # (S, H)
        with jax.named_scope("rope"):
            tables = afmoe.rope_tables(cfg, pos)
        leaves = {name: list(cache[name]) for name in ("k", "v")}
        counted = jnp.zeros((3,), jnp.int32)
        rows = jnp.zeros((2,), jnp.int32)       # (sliding, full) in use
        for li, layer in enumerate(params["layers"]):
            sliding = cfg.is_sliding(li)
            r = leaves["k"][li].shape[1]
            # rows 0..pos are in use (the current write included); a lane
            # frozen at the end of its rung (pos == C) keeps all of them
            in_use = jnp.minimum(pos + 1, r)                    # (S,)
            write = pos % cfg.sliding_window if sliding else pos
            with jax.named_scope(f"layer{li}"):
                x, (leaves["k"][li], leaves["v"][li]), counts = \
                    afmoe.decode_layer(
                        cfg, li, layer, x, tables,
                        (leaves["k"][li], leaves["v"][li]), write, in_use,
                        self.attn_impl)
            rows = rows.at[0 if sliding else 1].add(in_use.sum())
            if counts is not None:
                counted = counted + counts
        counts = cache["counts"] + jnp.concatenate([counted, rows])
        return afmoe.logits(cfg, params, x), {**leaves, "counts": counts}

    def prefill(self, margs, cache, slot, prompt, plen):
        """The full forward over one length-bucketed prompt (P,), sliding
        layers through the banded kernel, both kernels over the tile pairs
        up to plen alone (`afmoe.sequence_attention`), then the graft of
        the slot's
        rows a layer: a full layer's rows 0..P-1 (rows beyond plen hold
        padding garbage that the decode step never reads: it attends rows
        up to `pos`), a sliding layer's ring row r the prompt's position
        `plen - 1 - ((plen - 1 - r) mod W)`, the last W REAL positions (a
        row whose position would be negative is written before it is
        read). Returns the logits at plen - 1."""
        (params,) = margs
        cfg = self.cfg
        x, states = afmoe.encode(cfg, params, prompt, impl=self.attn_impl,
                                 plen=plen)
        leaves = {name: list(cache[name]) for name in ("k", "v")}
        for li, state in enumerate(states):
            with jax.named_scope(f"layer{li}"), jax.named_scope("attn"), \
                    jax.named_scope("kv_write"):
                if cfg.is_sliding(li):
                    r = jnp.arange(leaves["k"][li].shape[1])
                    at = plen - 1 - (plen - 1 - r) % cfg.sliding_window
                    state = tuple(jnp.take(part, jnp.maximum(at, 0), axis=0)
                                  for part in state)
                for name, part in zip(("k", "v"), state):
                    leaf = leaves[name][li]
                    leaves[name][li] = lax.dynamic_update_slice(
                        leaf, part[None].astype(leaf.dtype), (slot, 0, 0))
        h_last = jnp.take(x, plen - 1, axis=0)                  # (H,)
        return {**cache, **leaves}, afmoe.logits(cfg, params, h_last)


class Lfm2MoeDecoder:
    """Decode over a `models/lfm2_moe.py` parameter tree: gated short
    convolutions and grouped-query attention layers, each with the state
    its kind needs.

    The cache pytree: `{"k": [...], "v": [...]}`, one `(S, C, Hkv·Dh)` K
    and one V leaf an ATTENTION layer in the compute dtype, rows major;
    `"conv"`, one `(S, K-1, H)` tail a CONV layer in the compute dtype;
    `"counts"`, the running counts (`counter_names`). Only the K/V leaves
    know the rung.

    The full-sequence reference this must match is `deeplearning4j_tpu.
    models.lfm2_moe.forward` over the same prompt+generated prefix (and,
    outside the package, the plain reference under `benchmarks/families/`)."""

    uses_cache_rungs = True
    n_model_args = 1
    supports_draft = False
    max_cache_len = None        # rotary positions: no table bounds them
    counter_names = ("moe_pairs", "moe_expert_reads", "moe_pairs_max",
                     "global_rows_attended")

    def __init__(self, cfg, params, attn_impl="auto"):
        if attn_impl not in ("auto", "dense", "pallas"):
            raise ValueError(
                f"attn_impl must be 'auto', 'dense' or 'pallas', "
                f"got {attn_impl!r}")
        self.cfg = cfg
        self.params = params
        self.attn_impl = attn_impl
        self.vocab_size = int(cfg.vocab_size)
        # layer index -> index among the layers of its kind (its leaves)
        self._leaf = [sum(cfg.is_attention(j) == cfg.is_attention(li)
                          for j in range(li))
                      for li in range(cfg.num_hidden_layers)]

    def fingerprint(self):
        parts = ("lfm2-moe-decode", repr(self.cfg), self.attn_impl,
                 _shape_tree_repr(self.params),
                 _shape_tree_repr(
                     jax.eval_shape(lambda: self.init_cache(1, 1))))
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]

    def expert_runs(self, bucket):
        """How many times one expert layer of an admit of `bucket` tokens
        reads the layer's experts (`decoder_common.expert_runs`)."""
        return decoder_common.expert_runs(self.cfg,
                                          _expert_layer(self.params), bucket)

    def prefill_attn_pairs(self, plen, bucket):
        """(run, static) grid steps of one head of the prefill kernel over
        an admit of `plen` tokens in `bucket`, the attention layers' causal
        calls summed (`decoder_common.prefill_attn_pairs`); None off the
        kernel."""
        cfg = self.cfg
        return decoder_common.prefill_attn_pairs(
            self.attn_impl, plen, bucket, lfm2_moe.PREFILL_BLOCK,
            [None] * sum(map(cfg.is_attention,
                             range(cfg.num_hidden_layers))))

    def model_args(self):
        return (self.params,)

    def init_cache(self, slots, cache_len):
        cfg = self.cfg
        dt = cfg.compute_dtype
        na = sum(map(cfg.is_attention, range(cfg.num_hidden_layers)))
        nc = cfg.num_hidden_layers - na

        def leaves(n, shape):
            return [jnp.zeros((slots,) + shape, dt) for _ in range(n)]

        return {"k": leaves(na, (cache_len, cfg.kv_width)),
                "v": leaves(na, (cache_len, cfg.kv_width)),
                "conv": leaves(nc, (cfg.conv_L_cache - 1, cfg.hidden_size)),
                "counts": jnp.zeros((len(self.counter_names),), jnp.int32)}

    def grow(self, cache, new_len):
        """The K/V leaves padded to the longer rung; the tails and the
        counts as they are."""
        def pad(t):
            return jnp.pad(t, ((0, 0), (0, int(new_len) - t.shape[1]),
                               (0, 0)))
        return {**cache, "k": [pad(t) for t in cache["k"]],
                "v": [pad(t) for t in cache["v"]]}

    def counters(self, cache):
        return cache["counts"]

    def step(self, margs, cache, tokens, pos):
        """One decode step for the whole batch: a conv layer shifts its
        slots' tails by the token; an attention layer writes its K and V
        rows at `pos` and attends the rows in use, `min(pos + 1, C)`; then
        the feed-forward, dense or experts. Returns next-token logits (S,
        V). Counted a step, slots and layers summed: the attention layers'
        rows in use."""
        (params,) = margs
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = lfm2_moe.embed(params, tokens)                  # (S, H)
        with jax.named_scope("rope"):
            tables = lfm2_moe.rope_tables(cfg, pos)
        leaves = {name: list(cache[name]) for name in ("k", "v", "conv")}
        counted = jnp.zeros((3,), jnp.int32)
        rows = jnp.zeros((), jnp.int32)
        for li, layer in enumerate(params["layers"]):
            i = self._leaf[li]
            with jax.named_scope(f"layer{li}"):
                if cfg.is_attention(li):
                    # rows 0..pos are in use (the current write included);
                    # a lane frozen at the end of its rung (pos == C)
                    # keeps all of them
                    in_use = jnp.minimum(pos + 1, leaves["k"][i].shape[1])
                    x, (leaves["k"][i], leaves["v"][i]), counts = \
                        lfm2_moe.decode_layer(
                            cfg, li, layer, x, tables,
                            (leaves["k"][i], leaves["v"][i]), pos, in_use,
                            self.attn_impl)
                    rows = rows + in_use.sum()
                else:
                    x, leaves["conv"][i], counts = lfm2_moe.decode_layer(
                        cfg, li, layer, x, tables, leaves["conv"][i], pos,
                        None, self.attn_impl)
            if counts is not None:
                counted = counted + counts
        counts = cache["counts"] + jnp.concatenate([counted, rows[None]])
        return lfm2_moe.logits(cfg, params, x), {**leaves, "counts": counts}

    def prefill(self, margs, cache, slot, prompt, plen):
        """The full forward over one length-bucketed prompt (P,), attention
        through the causal prefill kernel over the tile pairs up to plen
        alone (`lfm2_moe.sequence_attention`), then the graft of the slot's
        state a layer: an attention layer's K/V rows 0..P-1 (rows beyond
        plen hold padding garbage that the decode step never reads: it
        attends rows up to `pos`), a conv layer's tail as the last REAL
        positions left it (z of positions plen - K + 1 .. plen - 1, zeros
        before 0; the bucket's last rows would be padding). Returns the
        logits at plen - 1."""
        (params,) = margs
        cfg = self.cfg
        x, states = lfm2_moe.encode(cfg, params, prompt, plen,
                                    impl=self.attn_impl)
        leaves = {name: list(cache[name]) for name in ("k", "v", "conv")}

        def graft(name, i, part):
            leaf = leaves[name][i]
            leaves[name][i] = lax.dynamic_update_slice(
                leaf, part[None].astype(leaf.dtype), (slot, 0, 0))

        for li, state in enumerate(states):
            i = self._leaf[li]
            with jax.named_scope(f"layer{li}"):
                if cfg.is_attention(li):
                    with jax.named_scope("attn"), \
                            jax.named_scope("kv_write"):
                        graft("k", i, state[0])
                        graft("v", i, state[1])
                else:
                    with jax.named_scope("shortconv"), \
                            jax.named_scope("tail_write"):
                        graft("conv", i, state)
        h_last = jnp.take(x, plen - 1, axis=0)                  # (H,)
        return {**cache, **leaves}, lfm2_moe.logits(cfg, params, h_last)


class RecurrentDecoder:
    """Carry-state decode over a recurrent `MultiLayerNetwork` (stacked
    LSTM/GRU/SimpleRnn + an RnnOutputLayer-style dense head, e.g. the
    zoo's TextGenerationLSTM).

    Tokens enter as one-hot feature vectors (char-RNN convention:
    head nOut == input feature width == vocab). The decode state is the
    recurrent carries, threaded through the network's OWN
    `_forward(carries=...)` path — a decode step is literally a T=1
    scan, so carries and logits are bit-identical to the full-sequence
    forward."""

    uses_cache_rungs = False
    n_model_args = 2

    def __init__(self, net):
        self.net = net
        layers = net.layers
        head = layers[-1]
        if not hasattr(head, "pre_activation"):
            raise ValueError(
                f"RecurrentDecoder needs a dense (RnnOutputLayer-style) "
                f"head with pre_activation; got {type(head).__name__}")
        rec = [l for l in layers[:-1]
               if getattr(l, "is_recurrent", False)]
        if not rec:
            raise ValueError(
                "RecurrentDecoder needs at least one recurrent layer")
        for l in rec:
            if not hasattr(l, "scan_apply"):
                raise ValueError(
                    f"{type(l).__name__} cannot run step-by-step "
                    "(no carried-state protocol)")
        it = getattr(net.conf, "input_type", None)
        if it is None or not hasattr(it, "size"):
            raise ValueError(
                "net conf has no sized recurrent InputType")
        self.n_features = int(it.size)
        self.vocab_size = int(head.nOut)
        if self.vocab_size != self.n_features:
            raise ValueError(
                f"char-RNN generation feeds sampled tokens back as "
                f"one-hot inputs: head nOut ({self.vocab_size}) must "
                f"equal the input feature width ({self.n_features})")
        # carry state is O(1) in sequence length: cache rungs are
        # meaningless — the server collapses them to a single rung that
        # only bounds prompt_len + max_new_tokens
        self.max_cache_len = None

    def fingerprint(self):
        from deeplearning4j_tpu.runtime.executables import \
            model_fingerprint
        return hashlib.sha256(
            ("recurrent-decode-" + model_fingerprint(self.net)).encode()
        ).hexdigest()[:16]

    def model_args(self):
        return (self.net._params, self.net._state)

    def init_cache(self, slots, cache_len):
        carries = {}
        for i, layer in enumerate(self.net.layers):
            if getattr(layer, "is_recurrent", False):
                carries[str(i)] = layer.zero_carry(int(slots))
        return {"carries": carries}

    def grow(self, cache, new_len):
        return cache    # carry state is length-independent

    def step(self, margs, cache, tokens, pos):
        """One decode step: one-hot the current tokens, run a T=1 pass
        through the network's carried-state forward, return the head's
        pre-activation logits (softmax-free: sampling works on logits)
        and the advanced carries.

        The step runs under an all-ones validity mask so it compiles
        into the SAME masked-scan graph family as the bucketed prefill
        and the canonical masked full-sequence forward — XLA fuses the
        gate math identically across that family (tested), which is
        what makes decode carries/logits BIT-identical to the
        full-sequence recompute rather than merely close."""
        params, state = margs
        s = tokens.shape[0]
        x = jax.nn.one_hot(tokens, self.n_features,
                           dtype=jnp.float32)[:, None, :]    # (S, 1, F)
        _, preact, _, _, carries = self.net._forward(
            params, state, x, False, None,
            mask=jnp.ones((s, 1), jnp.float32),
            carries=cache["carries"])
        return preact[:, 0].astype(jnp.float32), {"carries": carries}

    def prefill(self, margs, cache, slot, prompt, plen):
        """Run the length-bucketed prompt through the full scan under a
        validity mask (masked steps HOLD the carry — the recurrent
        layers' own masking contract), then graft the resulting carry
        rows into the slot. Returns the logits at the last real step."""
        params, state = margs
        p_len = prompt.shape[0]
        x = jax.nn.one_hot(prompt, self.n_features,
                           dtype=jnp.float32)[None]          # (1, P, F)
        mask = (jnp.arange(p_len)[None, :] < plen).astype(jnp.float32)
        _, preact, _, _, fresh = self.net._forward(
            params, state, x, False, None, mask=mask, carries={})
        carries = {}
        for idx, rows in cache["carries"].items():
            carries[idx] = tuple(
                lax.dynamic_update_slice(
                    full, one.astype(full.dtype),
                    (slot,) + (0,) * (full.ndim - 1))
                for full, one in zip(rows, fresh[idx]))
        logits = jnp.take(preact[0], plen - 1, axis=0).astype(jnp.float32)
        return {"carries": carries}, logits
