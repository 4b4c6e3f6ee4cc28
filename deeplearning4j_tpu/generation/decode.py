"""Decode-mode forwards: incremental single-token model evaluation over
donated device state.

Three kinds of adapter expose one contract to the GenerationServer:

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

- **FamilyDecoder** — the served language-model families under
  `models/` (`NemotronHDecoder`, `KeyeDecoder`, `MLADecoder`,
  `AfmoeDecoder`, `Lfm2MoeDecoder`, each a few lines naming its family
  module, its fingerprint's name and its device counters). A family
  DECLARES what a slot's state is, per layer: its leaves
  (`decoder_common.Leaf`), each a cache key, a width and a dtype, and
  rows at a rung of C that are `C / per_row` (two positions packed a row:
  Keye's index keys, MLA's latent rows), capped at a ring's rows (Trinity's
  sliding layers) or none (a Mamba-2 state, a convolution tail). It gives
  the step's hooks (what every layer of a step shares, one layer's decode
  under its own `layer<i>` scope, the counts after the step) and the
  prefill's (its forward over the prompt, what of each layer's state its
  leaves take). From that the generic decoder derives the cache, `grow`,
  the prefill's graft, the fingerprint and the counters. Adding a family
  costs a family file with its declaration and hooks, and a subclass of a
  few lines. BertDecoder and RecurrentDecoder keep classes of their own:
  BERT's paged pool, int8 scales, `verify` and drafting are not slot leaves
  of the declared kinds yet, and a recurrent net's state is its layers'
  carries, not leaves.

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
    flash_attention, flash_attention_decode, flash_attention_decode_mq,
    flash_attention_decode_mq_paged, flash_attention_decode_paged)
from deeplearning4j_tpu.models import (afmoe, decoder_common, deepseek_v3,
                                       keye_vl, lfm2_moe, nemotron_h)
from deeplearning4j_tpu.models.bert import (_ffn, _layer_norm,
                                            bert_mlm_logits)
from deeplearning4j_tpu.parallel.ring_attention import dense_attention

__all__ = ["AfmoeDecoder", "BertDecoder", "FamilyDecoder", "KeyeDecoder",
           "Lfm2MoeDecoder", "MLADecoder", "NemotronHDecoder",
           "RecurrentDecoder"]


def _shape_tree_repr(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return repr((str(treedef),
                 tuple((tuple(l.shape), str(jnp.result_type(l)))
                       for l in leaves)))


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
        cache["k"][li], cache["v"][li] = decoder_common.write_rows(
            (cache["k"][li], cache["v"][li]), wi, wj, (k, v))

    def _write_index(self, cache, pos, ptab):
        """Where rows at per-slot positions `pos` ((S,) or an (S, d)
        draft block) land: (`wi`, `wj`) = (slot, position), or (page,
        offset) through `ptab` when paged — and the row count C of the
        view attention reads. Paged writes past the mapped view (a
        frozen lane at pos == C) are redirected to the null page: a
        dense cache silently DROPS that out-of-range scatter; pages must
        redirect it explicitly or the clamped index would corrupt a
        live row."""
        ar = decoder_common.slot_index(pos)
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


#: what every expert layer counts on the device (`routed_experts`)
_MOE_COUNTERS = ("moe_pairs", "moe_expert_reads", "moe_pairs_max")


class FamilyDecoder:
    """Decode over a served family's parameter tree from the slot state
    the family declares. A subclass names the family's module (`family`),
    the fingerprint's name (`name`) and the counts its step keeps on the
    device (`counter_names`); the module gives:

    - `decode_leaves(cfg)`: for each layer, the `decoder_common.Leaf`s it
      owns. A layer's leaf of a name is the cache's leaf at the number of
      earlier layers that own a leaf of that name;
    - `embed(cfg, params, ids)` and `logits(cfg, params, x)`;
    - the step's hooks: `decode_context(cfg, pos, leaves, counts)` ->
      (what every layer of the step shares, the running tally),
      `decode_layer(cfg, li, layer, x, ctx, state, tally, impl)` -> (x,
      the layer's leaves, tally), in its own scope `layer<li>`, and
      `decode_counts(cfg, ctx, tally)` -> the counts after the step;
    - the prefill's: `encode_prompt(cfg, params, prompt, plen, impl)` ->
      (x, every layer's decode state) and `cache_parts(cfg, li, state,
      plen, leaves)` -> what of a layer's state its `leaves` take, in their
      order;
    - `expert_runs(cfg, layer, t)`, and where its prefill takes the causal
      kernel, `PREFILL_BLOCK` and `prefill_windows(cfg)`.

    The cache pytree: `{name: [one leaf a layer that owns one]}` for every
    declared name, and `"counts"` (`counter_names`). The full-sequence
    reference this must match is the family's `forward` over the same
    prompt+generated prefix (and, outside the package, the plain reference
    under `benchmarks/families/`)."""

    uses_cache_rungs = True
    n_model_args = 1
    supports_draft = False      # no model's prediction module is run
    max_cache_len = None        # no position table bounds the length

    def __init__(self, cfg, params, attn_impl="auto"):
        if attn_impl not in ("auto", "dense", "pallas"):
            raise ValueError(
                f"attn_impl must be 'auto', 'dense' or 'pallas', "
                f"got {attn_impl!r}")
        self.cfg = cfg
        self.params = params
        self.attn_impl = attn_impl
        self.vocab_size = int(cfg.vocab_size)
        # a name -> its leaves, one a layer that owns one; a layer -> each
        # leaf it owns with its index among the leaves of its name
        self._leaves, self._owned = {}, []
        for own in self.family.decode_leaves(cfg):
            self._owned.append(tuple(
                (leaf, len(self._leaves.setdefault(leaf.name, [])))
                for leaf in own))
            for leaf in own:
                self._leaves[leaf.name].append(leaf)

    def fingerprint(self):
        # the cache tree is part of every executable's signature, at the
        # least rung every leaf takes
        rung = max([1] + [leaf.per_row for leaves in self._leaves.values()
                          for leaf in leaves])
        parts = (self.name, repr(self.cfg), self.attn_impl,
                 _shape_tree_repr(self.params),
                 _shape_tree_repr(
                     jax.eval_shape(lambda: self.init_cache(1, rung))))
        return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]

    def expert_runs(self, bucket):
        """How many times one expert layer of an admit of `bucket` tokens
        reads the layer's experts (the family's `expert_runs`)."""
        return self.family.expert_runs(
            self.cfg, decoder_common.expert_layer(self.params), bucket)

    def prefill_attn_pairs(self, plen, bucket):
        """(run, static) grid steps of one head of the prefill kernels over
        an admit of `plen` tokens in `bucket`, every causal call summed
        (`decoder_common.prefill_attn_pairs`); None off the kernel, and
        where the family's prefill takes none."""
        windows = getattr(self.family, "prefill_windows", None)
        if windows is None:
            return None
        return decoder_common.prefill_attn_pairs(
            self.attn_impl, plen, bucket, self.family.PREFILL_BLOCK,
            windows(self.cfg))

    def model_args(self):
        return (self.params,)

    def init_cache(self, slots, cache_len):
        cache = {name: [jnp.zeros(leaf.shape(slots, cache_len), leaf.dtype)
                        for leaf in leaves]
                 for name, leaves in self._leaves.items()}
        cache["counts"] = jnp.zeros((len(self.counter_names),), jnp.int32)
        return cache

    def grow(self, cache, new_len):
        """Every leaf that holds rows padded to its rows at the longer rung
        (a ring up to its cap: it held positions 0..C - 1 at their own
        rows, none wrapped); the states, the tails and the counts as they
        are."""
        def pad(leaf, t):
            rows = leaf.shape(1, new_len)[1]
            return jnp.pad(t, ((0, 0), (0, rows - t.shape[1]))
                           + ((0, 0),) * len(leaf.width))
        return {**cache, **{
            name: [pad(leaf, t) for leaf, t in zip(leaves, cache[name])]
            for name, leaves in self._leaves.items() if leaves[0].per_row}}

    def counters(self, cache):
        return cache["counts"]

    def step(self, margs, cache, tokens, pos):
        """One decode step for the whole batch: each layer advances the
        leaves it owns by the token at `pos` and the tally takes what it
        counted (`decode_layer`). Returns next-token logits (S, V)."""
        (params,) = margs
        fam, cfg = self.family, self.cfg
        with jax.named_scope("embed"):
            x = fam.embed(cfg, params, tokens)                  # (S, H)
        leaves = {name: list(cache[name]) for name in self._leaves}
        ctx, tally = fam.decode_context(cfg, pos, leaves, cache["counts"])
        for li, (layer, own) in enumerate(zip(params["layers"],
                                              self._owned)):
            x, state, tally = fam.decode_layer(
                cfg, li, layer, x, ctx,
                tuple(leaves[leaf.name][i] for leaf, i in own), tally,
                self.attn_impl)
            for (leaf, i), new in zip(own, state):
                leaves[leaf.name][i] = new
        counts = fam.decode_counts(cfg, ctx, tally)
        return fam.logits(cfg, params, x), {**leaves, "counts": counts}

    def prefill(self, margs, cache, slot, prompt, plen):
        """The family's full forward over one length-bucketed prompt (P,),
        then the graft of the slot's part of every leaf in the leaf's
        scope: a leaf that holds rows takes rows 0..P-1 (rows past plen
        hold padding garbage that the decode step never reads: it attends
        rows up to `pos`), a state or a tail what the last REAL position
        left (`cache_parts`). Returns the logits at plen - 1."""
        (params,) = margs
        fam, cfg = self.family, self.cfg
        x, states = fam.encode_prompt(cfg, params, prompt, plen,
                                      self.attn_impl)
        leaves = {name: list(cache[name]) for name in self._leaves}
        for li, (state, own) in enumerate(zip(states, self._owned)):
            if not own:
                continue
            with jax.named_scope(f"layer{li}/{own[0][0].scope}"):
                parts = fam.cache_parts(cfg, li, state, plen, tuple(
                    leaves[leaf.name][i] for leaf, i in own))
                for (leaf, i), part in zip(own, parts):
                    full = leaves[leaf.name][i]
                    if part.ndim < full.ndim:       # without its slot axis
                        part = part[None]
                    leaves[leaf.name][i] = lax.dynamic_update_slice(
                        full, part.astype(full.dtype),
                        (slot,) + (0,) * (full.ndim - 1))
        # (a batched encode hands back its batch of one)
        h_last = jnp.take(x[0] if x.ndim == 3 else x, plen - 1, axis=0)
        return {**cache, **leaves}, fam.logits(cfg, params, h_last)


class NemotronHDecoder(FamilyDecoder):
    """`models/nemotron_h.py`: K/V leaves for the attention layers beside
    Mamba-2 state and convolution-tail leaves, in one donated cache."""
    family = nemotron_h
    name = "nemotron-h-decode"
    counter_names = _MOE_COUNTERS


class KeyeDecoder(FamilyDecoder):
    """`models/keye_vl.py`: grouped-query attention over the cache rows a
    learned indexer selects."""
    family = keye_vl
    name = "keye-decode"
    counter_names = _MOE_COUNTERS + ("dsa_rows_scored", "dsa_rows_selected",
                                     "dsa_rows_read")


class MLADecoder(FamilyDecoder):
    """`models/deepseek_v3.py`: multi-head latent attention over one cached
    row a position."""
    family = deepseek_v3
    name = "mla-decode"
    counter_names = _MOE_COUNTERS + ("mla_rows_attended", "mla_rows_read")


class AfmoeDecoder(FamilyDecoder):
    """`models/afmoe.py`: sliding-window and full grouped-query attention
    layers, each with the cache its kind needs."""
    family = afmoe
    name = "afmoe-decode"
    counter_names = _MOE_COUNTERS + ("window_rows_attended",
                                     "global_rows_attended")


class Lfm2MoeDecoder(FamilyDecoder):
    """`models/lfm2_moe.py`: gated short convolutions and grouped-query
    attention layers, each with the state its kind needs."""
    family = lfm2_moe
    name = "lfm2-moe-decode"
    counter_names = _MOE_COUNTERS + ("global_rows_attended",)


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
