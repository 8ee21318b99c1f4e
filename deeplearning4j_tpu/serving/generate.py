"""Planet-scale decode path: paged KV cache + speculative decoding + int8.

The decode tier of the model server (docs/SERVING.md#paged-kv--speculative-
decode): a decoder-only LM as a ``MultiLayerNetwork`` of an embedding,
decoder blocks and a per-token logits head — ``BertEmbeddingLayer`` →
``TransformerEncoderBlock(causal=True)`` × N → ``RnnOutputLayer``
(``zoo.bert.Bert(causal=True, task="mlm")``), or ``TokenEmbeddingLayer`` →
``HybridDecoderBlock`` × N → ``NormedLogitsLayer`` (``zoo.KimiLinear``,
``zoo.Glm4MoeLite``, ``zoo.Jamba``), or
any layers that meet the BLOCK PROTOCOL below — served by compile-once
executables:

- **prefill** — one causal forward over the whole prompt. Prompt lengths
  round up to ``seq_buckets``; the prompt's K/V scatter into the paged
  block pool through each stream's page table (serving/paged.py).
- **decode_step** — one token per call over the page table: scatter the
  new token's K/V at its slot, then attend ``k_pos <= position`` in one
  block-chunked pass that stops where the batch's longest stream ends
  (ops/attention.paged_attention). The page table and the trip count
  are DATA, not shape, so ONE executable (per batch bucket) serves every
  mix of context lengths with zero steady-state recompiles — and the
  pool is shared, so memory scales with actual tokens, not ``streams ×
  max_length`` (the ``concurrent_streams_per_device`` headline).
- **verify** — the speculative-decoding window: a small DRAFT net
  (``Bert(causal=True)`` tiny, loaded per-model via the router) proposes
  ``spec_tokens`` greedy tokens one cheap step at a time; the TARGET
  verifies the whole window in ONE batched step through the paged cache
  and emits every leading token the draft got right plus one
  correction/bonus token from its own logits. Greedy speculative output
  is therefore TOKEN-IDENTICAL to greedy non-speculative output by
  construction — every emitted token is the target's own argmax —
  proven in tests/test_paged_decode.py including a draft that is always
  wrong (k rejections per round, still identical, just slower).
  Rejected tails roll back page-table state exactly: positions are host
  bookkeeping, and the stale K/V rows of rejected slots are provably
  overwritten before any read (serving/paged.py module doc).
  ``temperature > 0`` falls back to the plain per-token sampling loop —
  verify-consistent by construction (same program, same key stream as
  the non-speculative path).
- **self-draft** — the model's OWN next-token-prediction (MTP) module in
  place of a draft net (:class:`SelfDraft`, ``self_draft=``): it reads the
  target's last hidden state beside the token that followed, shares the
  target's embedding and head, and keeps one more ``"tokens"`` layer of
  latent rows in the SAME block pool, behind the streams' own page tables.
  One proposal a step (``spec_tokens`` 1), verified through the same window
  (W = 2) and accepted or corrected by the same logic: a step yields one or
  two tokens, each the target's own argmax
  (docs/SERVING.md#self-draft-the-models-own-mtp-head).

The block protocol (what ``_decoder_parts`` checks; docs/SERVING.md):

- the first layer embeds: ``apply(params, {}, tokens (B, T))`` → ``(x, _)``,
  ``embed_step(params, tokens (B,), positions (B,))`` → (B, H),
  ``embed_window(params, tokens (B, W), positions (B, W))`` → (B, W, H);
  ``max_position`` bounds a stream unless ``max_length`` is given;
- every middle layer is causal and caches: ``cache_kind`` (``"tokens"``,
  the default: rows behind the page tables; ``"state"``: one slot a
  stream), ``init_pool(n)`` → a dict of arrays with ``n`` slots, of the
  type the block chooses,
  ``prefill_paged(params, x, pool, where, mask=)`` → ``(x, pool)`` and
  ``decode_window_paged(params, x_w, pool, where, positions, block_size,
  limits=)`` → ``(x_w, pool)``. ``where`` is the streams' address in that
  layer's cache: for ``"tokens"`` the flat token slots (B, T) in prefill and
  the page tables (B, max_blocks) in a decode window; for ``"state"`` the
  state slots (B,) in both. A ``"state"`` layer also says what its prefill
  walks and what its counters are called (``state_walk``: a name, a unit,
  the unit's positions, whether counts sum over the layers; KDA walks
  ``chunks`` of 64 under ``serving.kda_*``, the state-space scan
  ``positions`` under ``serving.ssm_*``). A resumed or chunked prefill also
  needs ``prefill_resume_paged``, the contiguous engine (``paged=False``)
  ``init_cache``/``prefill``/``decode_step``;
- the last layer has ``_logits(params, x)``.

A pool dict may carry an ``"moe"`` entry: a routed feed-forward's int32
counters, added to inside the prefill and decode programs and read once a
batch with its tokens (``serving.moe_*_total``). It is no cache
(``paged.NOT_CACHE``): the pool's sizes, types and block copies pass it by.

A net with ``"state"`` layers (recurrent: the state cannot be shared,
copied or rolled back) refuses the prefix cache, copy-on-write, chunked
prefill and the speculative verify window (a draft net's and a self-draft's
alike) with a ``ValueError`` at construction: they need a state snapshot no
layer offers yet.

Admission: a batch whose streams cannot all get blocks sheds with
:class:`~deeplearning4j_tpu.serving.resilience.PoolExhaustedError`
(HTTP 429 + Retry-After, flight-recorder cause ``pool_exhausted``)
BEFORE any device work; blocks free on completion/eos (the decode loop
exits early once every live row emitted eos) and on shed.

Weight-only int8 (serving/quantize.py): ``quantize="int8"`` stores
resident int8 weights + per-channel scales and dequantizes inside these
same executables; the fp32 path is bit-unchanged.

Exactness contracts (tests/test_paged_decode.py + tests/test_serving.py):
greedy decode through the paged cache == greedy decode through the
contiguous r13 cache == greedy O(T²) full recompute, token-for-token.
``generate_full_recompute`` remains the oracle. All programs are plain
``jax.jit`` with trace markers, so the CompileWatcher (and
``serving.recompiles_total``) sees every signature they ever trace.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.bucketing import BucketingPolicy
from deeplearning4j_tpu.nn.decoder import SelfDraft
from deeplearning4j_tpu.ops import attention as attn_ops
from deeplearning4j_tpu.serving.paged import (NOT_CACHE, BlockPool,
                                              PoolExhaustedError, PrefixCache,
                                              cache_kind, default_pool_blocks)
from deeplearning4j_tpu.serving.quantize import maybe_quantize
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.compile_watcher import note_trace


_PAGED = ("init_pool", "prefill_paged", "decode_window_paged")
_CONTIGUOUS = ("init_cache", "prefill", "decode_step")


def _decoder_parts(net, what: str, paged: bool = True):
    """Validate a decoder-only MLN against the block protocol (module doc)
    and split it into (emb, blocks, head)."""
    layers = net.layers
    if not layers or not all(hasattr(layers[0], m) for m in
                             ("embed_step", "embed_window")):
        raise ValueError(f"{what} needs an embedding input layer with "
                         "embed_step/embed_window (BertEmbeddingLayer, "
                         "TokenEmbeddingLayer; e.g. zoo.bert.Bert("
                         "causal=True, task='mlm'))")
    blocks = layers[1:-1]
    need = _PAGED if paged else _CONTIGUOUS
    lacking = [type(b).__name__ for b in blocks
               if not all(hasattr(b, m) for m in need)]
    if not blocks or lacking:
        raise ValueError(f"{what} needs decoder-block middle layers with "
                         f"{'/'.join(need)} (TransformerEncoderBlock, "
                         f"HybridDecoderBlock); got {lacking or 'none'}")
    if not all(getattr(b, "causal", False) for b in blocks):
        raise ValueError(f"{what} needs causal=True blocks — a "
                         "bidirectional encoder cannot decode "
                         "autoregressively")
    if not hasattr(layers[-1], "_logits"):
        raise ValueError(f"{what} needs a per-token logits head "
                         "(RnnOutputLayer, task='mlm')")
    return layers[0], list(blocks), layers[-1]


class Generator:
    """Compile-once decode serving head over a decoder-only
    MultiLayerNetwork (module doc).

    ``batch_buckets`` / ``prefill_buckets`` default to the model conf's
    bucketing knobs (ONE policy source of truth with training and the
    classify tier); ``max_length`` defaults to the embedding layer's
    ``max_position`` and bounds prompt + generated tokens.

    Decode engine knobs: ``paged`` (default True — the r13 contiguous
    cache remains as ``paged=False``, the identity oracle), ``block_size``
    / ``pool_blocks`` (pool geometry; default pool holds the largest
    batch bucket at full context, so admission only bites when sized
    down deliberately), ``draft_net`` + ``spec_tokens`` (speculative
    decoding — the draft runs its own small contiguous cache),
    ``self_draft`` (:class:`SelfDraft`: the model's own MTP module drafts one
    token a step, its latent rows one more layer of the pool), and
    ``quantize`` ("int8" weight-only serving)."""

    def __init__(self, net, *, max_length: Optional[int] = None,
                 batch_buckets=None, prefill_buckets=None,
                 paged: bool = True, block_size: int = 16,
                 pool_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 draft_net=None, spec_tokens: int = 4,
                 self_draft: Optional[SelfDraft] = None,
                 quantize: Optional[str] = None,
                 model_id: str = ""):
        self.emb, self.blocks, self.head = _decoder_parts(net, "Generator",
                                                          bool(paged))
        self.net = net
        self.model_id = str(model_id)
        self.max_length = int(max_length or self.emb.max_position)
        #: layers that keep a per-stream state, counted by what each says
        #: of itself (``state_walk``); a net with any is recurrent (module
        #: doc: what it refuses)
        self._state_layers: Dict = {}
        for blk in self.blocks:
            if cache_kind(blk) == "state":
                walk = blk.state_walk
                self._state_layers[walk] = self._state_layers.get(walk, 0) + 1
        self.recurrent = bool(self._state_layers)
        if self.recurrent:
            for on, name in ((prefix_cache, "prefix_cache (and its "
                              "copy-on-write)"),
                             (prefill_chunk, "prefill_chunk"),
                             (draft_net is not None or self_draft is not None,
                              "speculative decoding (draft_net, "
                              "self_draft)")):
                if on:
                    raise ValueError(
                        f"{name} is not served on a net with recurrent "
                        "(per-stream state) layers: sharing, resuming or "
                        "rolling back a stream needs a snapshot of its "
                        "state, which no layer offers yet")
        #: the model's own MTP module as the draft (module doc), or None
        self.mtp = self_draft
        if self_draft is not None:
            # a module's row i holds the token AFTER it (t_{i+1}): a prefix
            # shared up to a block's end, or a chunk resumed there, would
            # need the next stream's or chunk's first token
            for on, name in ((not paged, "paged=False"),
                             (draft_net is not None, "a draft_net as well"),
                             (prefix_cache, "prefix_cache"),
                             (prefill_chunk, "prefill_chunk")):
                if on:
                    raise ValueError(
                        f"self_draft is not served with {name}: its latent "
                        "rows live in the paged pool, one draft proposes, "
                        "and a row that follows the NEXT token cannot be "
                        "shared or resumed at a block's end yet")
        conf_policy = BucketingPolicy.from_conf(getattr(net, "conf", None))
        if batch_buckets is None and conf_policy is not None:
            batch_buckets = conf_policy.batch_buckets
        if prefill_buckets is None and conf_policy is not None:
            prefill_buckets = conf_policy.seq_buckets
        self.policy = BucketingPolicy(
            batch_buckets=batch_buckets or "pow2",
            seq_buckets=prefill_buckets or "pow2")
        self.paged = bool(paged)
        self.block_size = int(block_size)
        self._qp = maybe_quantize(net, quantize, model_id=self.model_id)
        # contiguous programs: the paged=False engine, the full-recompute
        # oracle's prefill, and the draft substrate
        self._prefill_jit = jax.jit(self._prefill)
        self._decode_jit = jax.jit(self._decode)
        # the oracle's forward: the contiguous prefill, or every block's
        # ``apply`` where the blocks keep no contiguous cache
        self._oracle_jit = self._prefill_jit if all(
            hasattr(b, "init_cache") for b in self.blocks) \
            else jax.jit(self._forward)
        self.pool: Optional[BlockPool] = None
        if self.paged:
            # an AUTO-sized pool (pool_blocks=None) grows on demand
            # (_admit) instead of shedding — the r13 contiguous engine
            # never refused a batch for cache memory, and a dynamic
            # ("pow2") bucket policy has no largest batch to size for.
            # Admission control = the shed contract only applies when the
            # operator PINNED a budget.
            self._pool_auto = pool_blocks is None
            if pool_blocks is None:
                bb = self.policy.batch_buckets
                pool_blocks = default_pool_blocks(
                    bb if isinstance(bb, tuple) else (32,),
                    self.max_length, self.block_size)
            # one state slot a stream of the largest batch (recurrent nets)
            bb = self.policy.batch_buckets
            self.pool = BlockPool(self._cache_blocks(),
                                  block_size=self.block_size,
                                  num_blocks=int(pool_blocks),
                                  max_length=self.max_length,
                                  model_id=self.model_id,
                                  state_slots=max(bb) if isinstance(
                                      bb, tuple) else 32)
            #: a routed feed-forward's counters ride in its layer's pool
            self._moe_layers = [i for i, p in enumerate(self.pool.pools)
                                if "moe" in p]
            self._moe_seen = None
            self._moe_totals_jit = jax.jit(self._moe_totals)
            # pools are DONATED through the paged programs (the hot loop
            # must not copy the whole pool per token) — every call site
            # threads the returned pools back into self.pool.pools
            self._prefill_paged_jit = jax.jit(self._prefill_paged,
                                              donate_argnums=(1,))
            self._decode_paged_jit = jax.jit(self._decode_paged,
                                             donate_argnums=(1,))
            self._verify_paged_jit = jax.jit(self._verify_paged,
                                             donate_argnums=(1,))
            self._prefill_window_jit = jax.jit(self._prefill_window_paged,
                                               donate_argnums=(1,))
            self._copy_block_jit = jax.jit(self._copy_block,
                                           donate_argnums=(0,))
            self._mtp_prefill_jit = jax.jit(self._mtp_prefill,
                                            donate_argnums=(2,))
            self._mtp_draft_jit = jax.jit(self._mtp_draft,
                                          donate_argnums=(2,))
        # prefix cache (ISSUE 16 tentpole): a radix trie over prompt
        # prefixes → block chains, so N streams with a common head hold
        # ONE physical copy and resume prefill past it. Off by default —
        # the bit-path of prefix_cache=False is the r20 engine unchanged.
        self.prefix_cache = bool(prefix_cache) and self.paged
        self.cache: Optional[PrefixCache] = (
            PrefixCache(self.pool) if self.prefix_cache else None)
        # chunked prefill: cap the window width so a long-prompt burst
        # yields the device to queued decode batches between chunks
        if prefill_chunk is not None and not self.paged:
            raise ValueError("prefill_chunk needs paged=True (the chunk "
                             "window is a paged program)")
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        #: KV positions the decode/verify steps read, and what they would
        #: read at the declared max_length (pool_stats, _count_kv_read)
        self._kv_read = self._kv_declared = 0
        #: per kind of state layer: units (chunks, positions) its prefills
        #: held and declared, stream states its decode steps moved and the
        #: bucket declared
        self._walked = {walk: [0, 0, 0, 0] for walk in self._state_layers}
        #: nesting depth of generate() — > 1 while a chunk-yield runs a
        #: nested decode batch; nested runs never grow/reset the pool
        self._depth = 0
        #: bumped whenever the device pool buffers are replaced (growth /
        #: exception reset) — a chunk loop re-checks it after yielding
        self._pool_epoch = 0
        # speculative decoding: the draft is a plain contiguous-cache
        # generator over the (tiny) draft net — same bucket policy, so
        # draft prefill shapes always match the target's prep
        # (a self-draft proposes ONE token: its module predicts the token
        # after the next, no further)
        self.spec_tokens = 1 if self_draft is not None else int(spec_tokens)
        self.draft: Optional[Generator] = None
        if draft_net is not None:
            if not self.paged:
                raise ValueError("speculative decoding needs paged=True "
                                 "(the verify window is a paged program)")
            self.draft = Generator(
                draft_net, max_length=self.max_length,
                batch_buckets=self.policy.batch_buckets,
                prefill_buckets=self.policy.seq_buckets,
                paged=False, model_id=f"{self.model_id}/draft"
                if self.model_id else "")
            if self.draft.emb.max_position < self.max_length:
                raise ValueError(
                    f"draft net max_position {self.draft.emb.max_position} "
                    f"< target max_length {self.max_length}")

    # ----------------------------------------------------------- parameters
    def _raw_params(self):
        """What the traced programs take: the live fp32 tree (bit-unchanged
        legacy path) or the resident (int8 leaves, scales) pair."""
        if self._qp is None:
            return self.net.params
        return self._qp.args()

    def _params_of(self, raw):
        """Inside-jit: raw → the parameter tree the layers consume. For
        int8 this IS the in-forward dequantize (serving/quantize.py)."""
        if self._qp is None:
            return raw
        return self._qp.rebuild(raw)

    def _cache_blocks(self):
        """The layers that keep a cache in the pool: the net's blocks, and
        behind them a self-draft's one block."""
        if self.mtp is None:
            return self.blocks
        return self.blocks + [self.mtp.module.block]

    def _rest(self, pools):
        """The pools behind the net's blocks (a self-draft's layer), which
        the target's programs hand through as they came."""
        return list(pools[len(self.blocks):])

    # ------------------------------------------------------ traced programs
    def _prefill(self, raw, tokens, lengths):
        """Contiguous-cache prefill: tokens (B, T) int32, lengths (B,)
        int32 → (next-token logits (B, V), caches). Padding rows/positions
        are masked out of every attention read; the cache rows they write
        are overwritten by generation before they are ever visible
        (nn/transformer.py)."""
        note_trace("serving.prefill", tokens, lengths)  # trace-time only
        params = self._params_of(raw)
        b, t = tokens.shape
        x, _ = self.emb.apply(params[0], {}, tokens)
        pad_mask = (jnp.arange(t)[None, :]
                    < lengths[:, None]).astype(x.dtype)
        caches = []
        for i, blk in enumerate(self.blocks):
            cache = blk.init_cache(b, self.max_length, x.dtype)
            x, cache = blk.prefill(params[i + 1], x, cache, mask=pad_mask)
            caches.append(cache)
        h_last = x[jnp.arange(b), lengths - 1]
        logits = self.head._logits(params[-1], h_last)
        return logits, caches

    def _forward(self, raw, tokens, lengths):
        """The full-recompute oracle's forward for blocks without a
        contiguous cache (nn/decoder.py): every block's ``apply`` over the
        whole grown sequence -> (next-token logits (B, V), None)."""
        note_trace("serving.full_forward", tokens, lengths)
        params = self._params_of(raw)
        b, t = tokens.shape
        x, _ = self.emb.apply(params[0], {}, tokens)
        pad_mask = (jnp.arange(t)[None, :]
                    < lengths[:, None]).astype(x.dtype)
        for i, blk in enumerate(self.blocks):
            x, _ = blk.apply(params[i + 1], {}, x, mask=pad_mask)
        return self.head._logits(params[-1],
                                 x[jnp.arange(b), lengths - 1]), None

    def _decode(self, raw, caches, tokens, positions):
        """One contiguous-cache autoregressive step: tokens (B,) placed at
        per-row ``positions`` (B,) → (next-token logits (B, V), caches)."""
        note_trace("serving.decode_step", tokens, positions)
        params = self._params_of(raw)
        x = self.emb.embed_step(params[0], tokens, positions)[:, None, :]
        new_caches = []
        for i, blk in enumerate(self.blocks):
            x, cache = blk.decode_step(params[i + 1], x, caches[i], positions)
            new_caches.append(cache)
        logits = self.head._logits(params[-1], x[:, 0])
        return logits, new_caches

    @staticmethod
    def _address(tables):
        """``tables`` as the paged programs take it: the page tables
        (B, max_blocks), or on a recurrent net the pair (page tables, state
        slots (B,)) -> (tables, states or None)."""
        return tables if isinstance(tables, tuple) else (tables, None)

    @staticmethod
    def _where(blk, tables, states):
        """A stream's address in ``blk``'s cache (module doc)."""
        return states if cache_kind(blk) == "state" else tables

    def _trash_address(self, batch: int):
        """The address of ``batch`` rows that hold nothing: every table
        entry the trash block, every state slot the trash slot."""
        tables = jnp.zeros((batch, self.pool.max_blocks_per_stream),
                           jnp.int32)
        if self.recurrent:
            return tables, jnp.zeros((batch,), jnp.int32)
        return tables

    def _prefill_paged(self, raw, pools, tokens, lengths, tables):
        """Paged prefill: same causal forward as ``_prefill`` (the prompt
        attention runs over in-register K/V, so the logits are identical),
        with every position's K/V scattered through the page table.
        ``tables``: see :meth:`_address`."""
        note_trace("serving.prefill_paged", tokens, lengths)
        params = self._params_of(raw)
        b, t = tokens.shape
        x, _ = self.emb.apply(params[0], {}, tokens)
        pad_mask = (jnp.arange(t)[None, :]
                    < lengths[:, None]).astype(x.dtype)
        tables, states = self._address(tables)
        slots = attn_ops.paged_slots(
            tables, jnp.broadcast_to(jnp.arange(t), (b, t)), self.block_size)
        new_pools = []
        for i, blk in enumerate(self.blocks):
            x, pool = blk.prefill_paged(params[i + 1], x, pools[i],
                                        self._where(blk, slots, states),
                                        mask=pad_mask)
            new_pools.append(pool)
        h_last = x[jnp.arange(b), lengths - 1]
        logits = self.head._logits(params[-1], h_last)
        new_pools += self._rest(pools)
        if self.mtp is not None:
            # the last hidden state of every position: the module's input
            return logits, x, new_pools
        return logits, new_pools

    def _decode_paged(self, raw, pools, tables, tokens, positions, limits):
        """One paged autoregressive step (module doc). ``limits`` (B,) is
        each stream's last valid position — a row that finished while its
        batch keeps decoding redirects overrun writes to the trash block
        instead of clobbering a live slot."""
        note_trace("serving.decode_step_paged", tokens, positions)
        params = self._params_of(raw)
        x = self.emb.embed_step(params[0], tokens, positions)[:, None, :]
        pos_w = positions[:, None]
        tables, states = self._address(tables)
        new_pools = []
        for i, blk in enumerate(self.blocks):
            x, pool = blk.decode_window_paged(
                params[i + 1], x, pools[i], self._where(blk, tables, states),
                pos_w, self.block_size, limits=limits)
            new_pools.append(pool)
        logits = self.head._logits(params[-1], x[:, 0])
        return logits, new_pools + self._rest(pools)

    def _verify_paged(self, raw, pools, tables, window, positions0, limits):
        """Speculative verify: ``window`` (B, W) tokens at positions
        ``positions0 + [0..W)`` → per-position next-token logits
        (B, W, V) in ONE batched step. Window K/V are written first, each
        query attends ``k_pos <= its position`` — exactly the sequential
        decode-step semantics, batched over the window."""
        note_trace("serving.verify_paged", window, positions0)
        params = self._params_of(raw)
        w = window.shape[1]
        pos_w = positions0[:, None] + jnp.arange(w)[None, :]
        x = self.emb.embed_window(params[0], window, pos_w)
        new_pools = []
        for i, blk in enumerate(self.blocks):
            x, pool = blk.decode_window_paged(params[i + 1], x, pools[i],
                                              tables, pos_w, self.block_size,
                                              limits=limits)
            new_pools.append(pool)
        logits = self.head._logits(params[-1], x)
        new_pools += self._rest(pools)
        if self.mtp is not None:
            return logits, x, new_pools
        return logits, new_pools

    def _mtp_prefill(self, raw, mtp_params, pools, tokens, lengths, tables,
                     hidden, cur):
        """The self-draft over whole prompts, behind the target's prefill:
        at position i the module reads ``hidden`` (B, T, H) there and the
        token at i + 1 (``cur`` (B,), the target's pick, behind the last
        prompt token), writes its latent row i through the same page tables
        and hands back its logits at each row's last position: the proposal
        for the token after ``cur``."""
        note_trace("serving.mtp_prefill", tokens, lengths)
        params, mod = self._params_of(raw), self.mtp.module
        b, t = tokens.shape
        rows = jnp.arange(b)
        pos = jnp.broadcast_to(jnp.arange(t), (b, t))
        nxt = jnp.roll(tokens, -1, axis=1).at[rows, lengths - 1].set(cur)
        x = mod.join(mtp_params,
                     self.emb.embed_window(params[0], nxt, pos), hidden)
        pad_mask = (pos < lengths[:, None]).astype(x.dtype)
        slots = attn_ops.paged_slots(tables, pos, self.block_size)
        x, pool = mod.block.prefill_paged(mtp_params["block"], x, pools[-1],
                                          slots, mask=pad_mask)
        logits = mod.logits(mtp_params, self.head, params[-1],
                            x[rows, lengths - 1])
        return logits, list(pools[:-1]) + [pool]

    def _mtp_draft(self, raw, mtp_params, pools, tables, after, hidden,
                   positions0, limits, take):
        """One self-draft step behind a verify window: the module reads the
        window's ``hidden`` (B, W, H) with the tokens that FOLLOW each
        position (``after`` (B, W): the target's own picks), writes its
        latent rows at the window's positions and hands back its logits at
        column ``take`` (B,), the last position the round committed. A row
        written past it is stale and overwritten by the next round before
        any read, as the target's are (serving/paged.py)."""
        note_trace("serving.mtp_draft", after, positions0)
        params, mod = self._params_of(raw), self.mtp.module
        b, w = after.shape
        pos_w = positions0[:, None] + jnp.arange(w)[None, :]
        x = mod.join(mtp_params,
                     self.emb.embed_window(params[0], after, pos_w), hidden)
        x, pool = mod.block.decode_window_paged(
            mtp_params["block"], x, pools[-1], tables, pos_w,
            self.block_size, limits=limits)
        logits = mod.logits(mtp_params, self.head, params[-1],
                            x[jnp.arange(b), take])
        return logits, list(pools[:-1]) + [pool]

    def _prefill_window_paged(self, raw, pools, window, positions, tables,
                              limits, last_idx):
        """Resume-from-position prefill over one chunk window: ``window``
        (B, W) prompt tokens at per-row absolute ``positions`` (B, W) —
        each row starts at its own cache-resume point — write-then-attend
        through the page table (``nn/transformer.py``
        ``prefill_resume_paged``), exactly the verify-window semantics,
        so chunked/resumed prefill gives whole prefill's tokens (its logits
        to the rounding of a float32 sum).
        ``limits`` (B,) = last prompt position (overrun/padding columns
        scatter to trash); ``last_idx`` (B,) selects each row's final-
        prompt-position column for the next-token logits (garbage for
        rows whose prompt ends in another chunk — the host keeps only
        the chunk where each row finishes). Everything but the (batch
        bucket, W-bucket) shape is data: ONE executable per bucket pair,
        zero steady-state recompiles across any hit/miss mix."""
        note_trace("serving.prefill_window_paged", window, positions)
        params = self._params_of(raw)
        # clamp: lockstep chunking runs padding columns past the prompt
        # (and past max_length for short rows) — limit-masked to trash on
        # write, never read back, but the gathers need in-range indices
        pos_w = jnp.minimum(positions, self.max_length - 1)
        x = self.emb.embed_window(params[0], window, pos_w)
        new_pools = []
        for i, blk in enumerate(self.blocks):
            x, pool = blk.prefill_resume_paged(params[i + 1], x, pools[i],
                                               tables, pos_w,
                                               self.block_size,
                                               limits=limits)
            new_pools.append(pool)
        b = window.shape[0]
        h_last = x[jnp.arange(b), last_idx]
        logits = self.head._logits(params[-1], h_last)
        return logits, new_pools

    def _copy_block(self, pools, src, dst):
        """Copy-on-write device copy: duplicate physical block ``src``'s
        rows into ``dst`` across every layer's row pools (the COW
        split of serving/paged.py — the table already points at ``dst``;
        this fills it before the suffix prefill overwrites the one
        recomputed row). Block ids are data: one executable ever."""
        note_trace("serving.cow_copy", src, dst)
        bs = self.block_size
        rows_src = src * bs + jnp.arange(bs)
        rows_dst = dst * bs + jnp.arange(bs)
        return [{n: a if n in NOT_CACHE else a.at[rows_dst].set(a[rows_src])
                 for n, a in p.items()} for p in pools]

    # ------------------------------------------------------------- sampling
    @staticmethod
    def _sample(logits, temperature: float, key):
        if temperature and temperature > 0.0:
            return jax.random.categorical(
                key, logits / jnp.asarray(temperature, logits.dtype), axis=-1
            ).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _prefill_len(self, longest: int) -> int:
        """Prefill shape for the longest prompt: its seq bucket, with
        ``max_length`` as the implicit FINAL bucket — a prompt above the
        largest explicit bucket pads up to max_length instead of tracing a
        fresh per-length executable (the pad-up-not-retrace contract,
        docs/SERVING.md; warmup() primes the max_length shape too)."""
        t = self.policy.bucket_seq(longest)
        top = self.policy.seq_buckets
        if isinstance(top, tuple) and longest > top[-1]:
            return self.max_length
        return min(t, self.max_length)

    def _prep(self, prompts: Sequence[Sequence[int]], max_new_tokens: int):
        lens = [len(p) for p in prompts]
        if min(lens) < 1:
            raise ValueError("empty prompt")
        if max(lens) + max_new_tokens > self.max_length:
            raise ValueError(
                f"prompt ({max(lens)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_length ({self.max_length})")
        b_real = len(prompts)
        b = self.policy.bucket_batch(b_real)
        t = self._prefill_len(max(lens))
        tokens = np.zeros((b, t), np.int32)
        lengths = np.ones((b,), np.int32)  # padded rows: 1 fake token
        for i, p in enumerate(prompts):
            tokens[i, :lens[i]] = np.asarray(p, np.int32)
            lengths[i] = lens[i]
        return (jnp.asarray(tokens), jnp.asarray(lengths), b_real, lens)

    @staticmethod
    def _trim_row(row: List[int], max_new: int,
                  eos_id: Optional[int]) -> List[int]:
        row = row[:max_new]
        if eos_id is not None and eos_id in row:
            row = row[: row.index(eos_id) + 1]
        return row

    def _trim(self, stacked, b_real: int, lens, max_new_tokens: int,
              eos_id: Optional[int]) -> List[List[int]]:
        return [self._trim_row([int(v) for v in stacked[i]],
                               max_new_tokens, eos_id)
                for i in range(b_real)]

    # ------------------------------------------------------------ admission
    def _grow(self, need: int, need_states: int = 0):
        """Swap in a pool twice the size (or ``need`` blocks if larger;
        at least ``need_states`` state slots).
        Growth changes the pool shapes, so the NEXT paged calls trace once
        at the new size — a capacity event, not steady state (serving
        configs with finite buckets size the pool to their largest batch
        up front and never reach this branch; the 0-recompile contract is
        asserted there). Old buffers are dropped BEFORE the new
        allocation so device residency never doubles — which also kills
        every cached prefix byte, so the trie flushes first."""
        grown = max(need, 2 * self.pool.num_blocks)
        tm.counter("serving.kv_pool_grown_total", model=self.model_id)
        tm.instant("serving.kv_pool_grown", model=self.model_id,
                   blocks=grown)
        if self.cache is not None:
            self.cache.flush()
        old_peak = self.pool.peak_streams
        self.pool.pools = None  # free before the bigger alloc
        states = max(need_states, self.pool.num_state_slots)
        self.pool = BlockPool(self._cache_blocks(),
                              block_size=self.block_size,
                              num_blocks=grown,
                              max_length=self.max_length,
                              model_id=self.model_id, state_slots=states)
        self.pool.peak_streams = old_peak
        self._moe_seen = None
        self._pool_epoch += 1
        if self.cache is not None:
            self.cache.rebind(self.pool)

    def _admit(self, lens, max_new: int, batch: int, prompts=None):
        """Reserve every stream's blocks for the WHOLE generation —
        all-or-nothing (PoolExhaustedError → the scheduler's 429 shed) —
        and build the (B, max_blocks) page-table array. An AUTO-sized pool
        (no operator budget) GROWS to fit instead of shedding: reserve
        failed with nothing allocated and pool content never outlives a
        batch — except prefix-cache content, which the grow path flushes
        — so swapping in a larger pool is safe mid-flight. A NESTED batch
        (running inside another batch's chunk-yield, ``_depth > 1``)
        never grows: the outer prefill is mid-write into the current
        buffers.

        Returns ``(tables_list, tables, starts, cow, pending, held)``:
        per-stream block lists, the device table array, each stream's
        resume position (0 without a cache hit), COW ``(src, dst)`` block
        copies to run before prefill, the batch's pending trie nodes
        to commit after it, and the streams' state slots. On a recurrent
        net ``tables`` is the pair the paged programs take
        (:meth:`_address`): the table array and the state slots (B,)."""
        if self.cache is not None and prompts is not None:
            return (*self._admit_prefix(prompts, lens, max_new, batch), [])
        counts = [self.pool.blocks_needed(l, max_new) for l in lens]
        try:
            tables_list, held = self._reserve(counts)
        except PoolExhaustedError:
            if not self._pool_auto or self._depth > 1:
                raise
            self._grow(int(sum(counts)), len(counts))
            tables_list, held = self._reserve(counts)
        tables = jnp.asarray(self.pool.table_array(tables_list, batch))
        if self.recurrent:
            tables = (tables, jnp.asarray(self.pool.state_array(held, batch)))
        return tables_list, tables, [0] * len(lens), [], [], held

    def _reserve(self, counts):
        """Blocks and state slots of a batch, both or neither."""
        tables_list = self.pool.reserve(counts)
        try:
            return tables_list, self.pool.reserve_states(len(counts))
        except PoolExhaustedError:
            self.pool.release(tables_list)
            raise

    def _admit_prefix(self, prompts, lens, max_new: int, batch: int):
        """Prefix-aware admission: transactional match + reserve + COW +
        trie insert (``_admit_prefix_once``), with a retry ladder on
        exhaustion — evict cache-only blocks first, then (auto pools,
        non-nested only) grow."""
        worst = sum(self.pool.blocks_needed(l, max_new) for l in lens)
        try:
            return self._admit_prefix_once(prompts, lens, max_new, batch)
        except PoolExhaustedError:
            pass
        # second chance: LRU-evict blocks only the trie still holds
        self.cache.evict(worst)
        try:
            return self._admit_prefix_once(prompts, lens, max_new, batch)
        except PoolExhaustedError:
            if not self._pool_auto or self._depth > 1:
                raise
        self._grow(worst)
        return self._admit_prefix_once(prompts, lens, max_new, batch)

    def _admit_prefix_once(self, prompts, lens, max_new: int, batch: int):
        """One admission attempt, all-or-nothing ACROSS THE BATCH: on
        PoolExhaustedError every hold this attempt took — matched-prefix
        increfs, fresh reservations, COW splits, pending trie inserts —
        is rolled back before the raise, so the caller's retry ladder
        (and the 429 shed) always starts from clean allocator state."""
        pool, cache, bs = self.pool, self.cache, self.block_size
        tables_list, starts, cow, pending = [], [], [], []
        hit_tokens = 0
        with pool._lock:
            try:
                for p, l in zip(prompts, lens):
                    blocks, committed = cache.match(p)  # increfs matched
                    need = pool.blocks_needed(l, max_new)
                    try:
                        # matched < need always (max_new >= 1): every
                        # stream owns at least its generation blocks
                        fresh = pool.reserve([need - len(blocks)])[0]
                    except PoolExhaustedError:
                        pool.decref(blocks)  # match-only holds so far
                        raise
                    table = list(blocks) + fresh
                    # resume point: skip committed tokens, but always
                    # recompute >= 1 prompt token for next-token logits
                    start = min(committed, l - 1)
                    if start < committed:
                        # block-aligned full hit: the one recomputed
                        # position l-1 lands INSIDE a shared cached
                        # block — copy-on-write before the prefill
                        bi = start // bs
                        try:
                            nb = pool.cow_split(table[bi])
                        except PoolExhaustedError:
                            pool.release([table])
                            raise
                        cow.append((table[bi], nb))
                        table[bi] = nb
                    pending.extend(cache.insert(p, table))
                    tables_list.append(table)
                    starts.append(start)
                    hit_tokens += start
            except PoolExhaustedError:
                cache.rollback(pending)
                pool.release(tables_list)
                raise
        tm.gauge("serving.prefix_cache_hit_rate",
                 round(cache.hit_rate(), 4), model=self.model_id)
        self._last_hit_tokens = hit_tokens
        tables = jnp.asarray(pool.table_array(tables_list, batch))
        return tables_list, tables, starts, cow, pending

    # ------------------------------------------------------------- decoding
    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 16, *, temperature: float = 0.0,
                 key=None, eos_id: Optional[int] = None,
                 trace: bool = False, stats: Optional[Dict] = None,
                 yield_hook=None) -> List[List[int]]:
        """Decode ``prompts``: one prefill + per-token decode steps (or
        speculative verify windows when a draft net is attached and the
        decode is greedy), all on warmed executables. ``temperature=0`` is
        greedy (deterministic); otherwise categorical sampling from
        ``key`` (default PRNGKey(0)) through the plain per-token loop.
        ``trace=True`` (a head-sampled serving batch) emits prefill /
        ``verify`` spans (docs/OBSERVABILITY.md#request-tracing--slos).
        On a serving worker it also marks the worker's phases ``launch``
        (the prefill is out), ``wait`` (the last decode launch returned)
        and ``drain`` (the first fetch of the last step's output returned:
        ``t_done``), without moving a fetch. ``stats`` (a dict,
        filled in place) receives ``draft_accept_rate`` per row and the
        batch ``spec_accept_rate`` when speculating, plus
        ``prefix_hit_rate`` / ``resumed_positions`` / ``prefill_chunks``
        under the prefix cache / chunked prefill. ``yield_hook``
        (scheduler-provided) is called between prefill chunks so queued
        interactive decode batches can run mid-prefill."""
        if max_new_tokens < 1:
            return [[] for _ in prompts]
        if not self.paged:
            return self._generate_contiguous(
                prompts, max_new_tokens, temperature=temperature, key=key,
                eos_id=eos_id, trace=trace)
        tokens, lengths, b_real, lens = self._prep(prompts, max_new_tokens)
        batch = int(tokens.shape[0])
        self._depth += 1
        try:
            # admission stays OUTSIDE the reset-on-failure block: a shed
            # allocated nothing and must not trash live pool content
            tables_list, tables, starts, cow, pending, held = self._admit(
                lens, max_new_tokens, batch, prompts=prompts)
        except BaseException:
            self._depth -= 1
            raise
        if stats is not None and self.cache is not None:
            stats["prefix_hit_rate"] = round(
                sum(starts) / max(1, sum(lens)), 4)
            stats["resumed_positions"] = list(starts)
        try:
            speculate = ((self.draft is not None or self.mtp is not None)
                         and self.spec_tokens > 0
                         and not (temperature and temperature > 0.0))
            if speculate:
                return self._generate_speculative(
                    tokens, lengths, tables, b_real, lens, max_new_tokens,
                    eos_id=eos_id, trace=trace, stats=stats,
                    starts=starts, cow=cow, pending=pending,
                    yield_hook=yield_hook)
            return self._generate_paged(
                tokens, lengths, tables, b_real, lens, max_new_tokens,
                temperature=temperature, key=key, eos_id=eos_id,
                trace=trace, stats=stats, starts=starts, cow=cow,
                pending=pending, yield_hook=yield_hook)
        except BaseException:
            # a failure mid-decode may have consumed the donated pool
            # buffers — rebuild them (pool CONTENT never outlives a batch
            # except cached prefixes, which _reset_pools flushes; only
            # the host allocator state matters, and release() below
            # restores that)
            if self.cache is not None:
                self.cache.rollback(pending)
            self._reset_pools()
            raise
        finally:
            self._depth -= 1
            # blocks free on completion, eos early-exit, and shed alike
            self.pool.release(tables_list, held)

    def _reset_pools(self):
        if self.cache is not None:
            # the buffers the cached blocks lived in are being replaced
            self.cache.flush()
        self.pool.pools = self.pool.init_pools()
        self._moe_seen = None
        self._pool_epoch += 1

    def _window_width(self, max_rem: int) -> int:
        """Chunk-window width for ``max_rem`` remaining prompt tokens:
        the operator's ``prefill_chunk`` when set, else one bucketed
        window covering the whole remainder (suffix-only resume, no
        interleaving) — either way a shape warmup() primed."""
        if self.prefill_chunk is not None:
            return min(self.prefill_chunk, self.max_length)
        return self._prefill_len(max_rem)

    def _launch_prefill(self, raw, tokens, lengths, tables):
        """One whole-prompt prefill, launched and counted (rows x declared
        positions, and the launch: what one launch computed is their
        ratio). Returns (logits, the last hidden state of every position
        for a self-draft, else None)."""
        out = self._prefill_paged_jit(raw, self.pool.pools, tokens, lengths,
                                      tables)
        tm.phase("serving.generate.launch")
        self.pool.pools = out[-1]
        tm.counter("serving.prefill_positions_total",
                   int(tokens.shape[0]) * int(tokens.shape[1]),
                   model=self.model_id)
        tm.counter("serving.prefill_launches_total", model=self.model_id)
        return out[0], (out[1] if self.mtp is not None else None)

    def _run_prefill(self, raw, tokens, lengths, tables, b_real, lens,
                     starts, cow, pending, tele, stats, yield_hook,
                     speculative: bool = False):
        """Dispatch the prompt phase: COW block copies, then either the
        r20 whole-prompt prefill (bit-path unchanged — no cache hit, no
        chunking) or the resume/chunk window loop, then commit this
        batch's trie nodes. Returns next-token logits (B, V) and, for a
        self-draft, the last hidden state of every position (else None)."""
        batch = int(tokens.shape[0])
        t = int(tokens.shape[1])
        for src, dst in cow:
            pools = self._copy_block_jit(self.pool.pools,
                                         jnp.asarray(src, jnp.int32),
                                         jnp.asarray(dst, jnp.int32))
            self.pool.pools = pools
        t_pf = time.time_ns() if tele else 0
        whole = (not any(starts)) and (self.prefill_chunk is None
                                       or t <= self.prefill_chunk)
        hidden = None
        if whole:
            logits, hidden = self._launch_prefill(raw, tokens, lengths,
                                                  tables)
            n_chunks = 1
            self._count_state_prefill(batch, t, lens)
        else:
            logits, n_chunks = self._prefill_windowed(
                raw, tokens, lengths, tables, b_real, lens, starts,
                yield_hook)
        if tele:
            tele.event_deferred(
                "serving.generate.prefill", t_pf, time.time_ns(),
                batch=batch, seq=t, paged=True, speculative=speculative,
                prefix_hit=bool(any(starts)),
                resumed=int(sum(starts)), chunks=n_chunks)
        if stats is not None:
            stats["prefill_chunks"] = n_chunks
        if self.cache is not None and pending:
            # the prefill that writes these blocks has been issued —
            # program order guarantees any later read sees the writes
            self.cache.commit(pending)
        return logits, hidden

    def _prefill_windowed(self, raw, tokens, lengths, tables, b_real,
                          lens, starts, yield_hook):
        """The resume/chunk window loop (ISSUE 16): every row computes
        only its uncached suffix, ``W`` positions per chunk, through
        ``_prefill_window_paged``. Lockstep chunking — chunk c covers
        per-row absolute positions ``start_i + c*W + [0, W)`` — keeps
        shapes fixed; rows pad with trash-masked columns once their
        prompt is done. Between chunks ``yield_hook`` hands the device
        to queued interactive batches (chunked prefill: a long-prompt
        burst cannot spike decode p99); the pool-epoch check aborts if
        a nested run reset the buffers under us."""
        batch = int(tokens.shape[0])
        t = int(tokens.shape[1])
        tokens_np = np.asarray(tokens)
        lengths_np = np.asarray(lengths)
        starts_np = np.zeros((batch,), np.int32)
        starts_np[:b_real] = np.asarray(starts, np.int32)
        max_rem = max(int(l - s) for l, s in zip(lens, starts))
        w = self._window_width(max_rem)
        n_chunks = math.ceil(max_rem / w)
        limits = jnp.asarray((lengths_np - 1).astype(np.int32))
        final = np.zeros((batch,), object)
        for c in range(n_chunks):
            if c and yield_hook is not None:
                epoch0 = self._pool_epoch
                yield_hook()
                if self._pool_epoch != epoch0:
                    raise RuntimeError(
                        "KV pool reset during chunked-prefill yield — "
                        "aborting the outer batch")
            base = starts_np + c * w
            cols = base[:, None] + np.arange(w, dtype=np.int32)[None, :]
            window = np.take_along_axis(
                tokens_np, np.minimum(cols, t - 1), axis=1)
            window = np.where(cols < lengths_np[:, None], window, 0)
            li = lengths_np - 1 - base
            in_chunk = (li >= 0) & (li < w)
            last_idx = np.clip(li, 0, w - 1).astype(np.int32)
            logits_c, pools = self._prefill_window_jit(
                raw, self.pool.pools, jnp.asarray(window),
                jnp.asarray(cols), tables, limits,
                jnp.asarray(last_idx))
            tm.phase("serving.generate.launch")
            self.pool.pools = pools
            if in_chunk.any():
                # keep the device rows; host-gather only at the end
                rows = logits_c
                for i in np.nonzero(in_chunk)[0]:
                    final[i] = rows[i]
        tm.counter("serving.chunked_prefill_chunks_total", n_chunks,
                   model=self.model_id)
        logits = jnp.stack([final[i] for i in range(batch)])
        return logits, n_chunks

    def _kv_positions_read(self, batch: int, last_pos: int) -> int:
        """KV positions one paged step reads per K and V pool of a layer
        when its largest query position is ``last_pos``: batch x chunk x
        turns, the trip count ``ops/attention.paged_attention`` takes from
        the positions, worked out here from the same static shapes."""
        bs = self.block_size
        width = self.pool.max_blocks_per_stream
        cb = attn_ops.paged_chunk_blocks(batch, width, bs)
        turns = min(last_pos // (cb * bs) + 1, -(-width // cb))
        return batch * cb * bs * turns

    def _count_kv_read(self, batch: int, read: int, steps: int):
        """One batch's decode/verify steps onto the counters: what they
        read against ``steps`` x batch x max_length, what the gather of the
        declared length read at every step."""
        declared = steps * batch * self.max_length
        self._kv_read += read
        self._kv_declared += declared
        tm.counter("serving.decode_kv_positions_read_total", read,
                   model=self.model_id)
        tm.counter("serving.decode_kv_positions_declared_total", declared,
                   model=self.model_id)

    def _count_state_prefill(self, batch: int, t: int, lens):
        """One whole prefill onto the counters of each kind of state layer
        (``serving.<name>_prefill_<unit>_live_total`` / ``_declared_total``):
        the units of its walk that hold a token (chunks of 64 for KDA,
        positions for the state-space scan; a padding row of the bucket has
        one) against batch x units of the bucket, worked out here from the
        prompt lengths and what the block says it walks (``state_walk``)."""
        for walk, layers in self._state_layers.items():
            units = lambda n: -(-n // walk.size)
            times = layers if walk.per_layer else 1
            live = (sum(map(units, lens)) + batch - len(lens)) * times
            declared = batch * units(t) * times
            self._walked[walk][0] += live
            self._walked[walk][1] += declared
            stem = f"serving.{walk.name}_prefill_{walk.unit}"
            tm.counter(stem + "_live_total", live, model=self.model_id)
            tm.counter(stem + "_declared_total", declared,
                       model=self.model_id)

    def _count_state_decode(self, batch: int, b_real: int, steps: int):
        """One batch's decode steps onto the counters
        (``serving.<name>_decode_states_live_total`` / ``_declared_total``):
        the stream states a step reads and writes in place (a live row's,
        in every layer of that kind) against the bucket's rows, which a
        gather and scatter of the declared batch moved."""
        for walk, layers in self._state_layers.items():
            live, declared = steps * b_real * layers, steps * batch * layers
            self._walked[walk][2] += live
            self._walked[walk][3] += declared
            stem = f"serving.{walk.name}_decode_states"
            tm.counter(stem + "_live_total", live, model=self.model_id)
            tm.counter(stem + "_declared_total", declared,
                       model=self.model_id)

    @staticmethod
    def _moe_totals(pools):
        """The routed layers' counters, summed over the layers: (2, 5)
        int32, prefill and decode rows (nn/decoder.py)."""
        return sum(p["moe"] for p in pools if "moe" in p)

    def _count_moe(self):
        """One batch's router counts onto ``serving.moe_*_total``: the
        device accumulators wrap (int32), so the host adds differences
        modulo 2**32. One small program and one fetch a batch, launched
        behind the last decode step: that fetch is the batch's first of an
        output of its last step, so its return starts ``drain``."""
        if not self._moe_layers:
            return
        from deeplearning4j_tpu.nn.moe import MOE_STATS

        now = np.asarray(self._moe_totals_jit(self.pool.pools)).astype(
            np.uint32)
        tm.phase("serving.generate.drain")  # t_done on an expert net
        seen = self._moe_seen if self._moe_seen is not None \
            else np.zeros_like(now)
        self._moe_seen = now
        for phase, row in zip(("prefill", "decode"),
                              (now - seen).astype(np.int64)):
            for name, value in zip(MOE_STATS, row):
                tm.counter(f"serving.moe_{name}_total", int(value),
                           model=self.model_id, phase=phase)
            # the decode step's own: what its roofline reader divides by
            if phase == "decode":
                tm.counter("serving.moe_decode_experts_touched_total",
                           int(row[MOE_STATS.index("experts_touched")]),
                           model=self.model_id)
                tm.counter("serving.moe_decode_layer_steps_total",
                           int(row[-1]), model=self.model_id)

    def _generate_paged(self, tokens, lengths, tables, b_real, lens,
                        max_new: int, *, temperature: float, key,
                        eos_id: Optional[int], trace: bool, stats=None,
                        starts=(), cow=(), pending=(), yield_hook=None):
        """The plain per-token paged loop (greedy or sampled) — the same
        sampling stream as the contiguous path, so paged==contiguous is
        token-exact (greedy) / stream-exact (sampled)."""
        raw = self._raw_params()
        if key is None:
            key = jax.random.PRNGKey(0)
        tele = tm.get_telemetry() if trace else None
        batch = int(tokens.shape[0])
        limits = jnp.asarray(np.asarray(
            [l + max_new - 1 for l in lens]
            + [0] * (batch - b_real), np.int32))

        logits, _ = self._run_prefill(raw, tokens, lengths, tables, b_real,
                                      lens, starts, cow, pending, tele,
                                      stats, yield_hook)
        positions = lengths
        longest = max(lens)  # the batch's largest position, step 0
        kv_read = 0
        steps = []
        done = np.zeros(b_real, bool)
        key, sub = jax.random.split(key)
        cur = self._sample(logits, temperature, sub)
        for i in range(max_new):
            steps.append(cur)
            if eos_id is not None:
                done |= (np.asarray(cur)[:b_real] == eos_id)
                if done.all():
                    break  # every live stream finished: free blocks early
            if i == max_new - 1:
                break
            logits, pools = self._decode_paged_jit(
                raw, self.pool.pools, tables, cur, positions, limits)
            self.pool.pools = pools
            kv_read += self._kv_positions_read(batch, longest + i)
            positions = positions + 1
            key, sub = jax.random.split(key)
            cur = self._sample(logits, temperature, sub)
        tm.phase("serving.generate.wait")
        self._count_kv_read(batch, kv_read, len(steps) - 1)
        self._count_state_decode(batch, b_real, len(steps) - 1)
        self._count_moe()
        fetched = [np.asarray(s) for s in steps]
        tm.phase("serving.generate.drain")  # t_done on a dense net
        stacked = np.stack(fetched, axis=1)
        return self._trim(stacked, b_real, lens, max_new, eos_id)

    def _generate_speculative(self, tokens, lengths, tables, b_real, lens,
                              max_new: int, *, eos_id: Optional[int],
                              trace: bool, stats: Optional[Dict],
                              starts=(), cow=(), pending=(),
                              yield_hook=None):
        """Greedy speculative decode (module doc). Every emitted token is
        the TARGET's argmax — the draft only decides how many the verify
        window can commit at once. Prefix sharing applies to the TARGET's
        paged prefill only; the draft keeps its own full contiguous
        prefill (its cache is private, tiny, and never shared). With a
        self-draft (module doc) the proposal is the model's own MTP
        module's: made behind the prefill and behind every verify window
        from the hidden states those hand back."""
        raw = self._raw_params()
        draft, mtp = self.draft, self.mtp
        draft_raw = draft._raw_params() if draft is not None else None
        tele = tm.get_telemetry() if trace else None
        batch = int(tokens.shape[0])
        w = self.spec_tokens + 1  # window = last accepted + k proposals
        limits_np = np.asarray([l + max_new - 1 for l in lens]
                               + [0] * (batch - b_real), np.int32)
        limits = jnp.asarray(limits_np)

        logits, hidden = self._run_prefill(
            raw, tokens, lengths, tables, b_real, lens, starts, cow, pending,
            tele, stats, yield_hook, speculative=True)
        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # token AT pos
        if mtp is None:
            _, dcaches = draft._prefill_jit(draft_raw, tokens, lengths)
        else:
            dlogits, self.pool.pools = self._mtp_prefill_jit(
                raw, mtp.params, self.pool.pools, tokens, lengths, tables,
                hidden, cur)
            proposal = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
        pos_np = np.asarray(lengths)  # cur's position, per row
        prev = tokens[jnp.arange(batch), jnp.asarray(pos_np) - 1]
        emitted: List[List[int]] = [[] for _ in range(batch)]
        done = np.zeros(b_real, bool)
        accept_num = np.zeros(batch, np.int64)
        accept_den = np.zeros(batch, np.int64)
        host_cur = np.asarray(cur)
        for i in range(b_real):
            emitted[i].append(int(host_cur[i]))
            if eos_id is not None and int(host_cur[i]) == eos_id:
                done[i] = True

        unfinished = lambda: not done.all() and any(
            len(emitted[i]) < max_new for i in range(b_real) if not done[i])
        rounds = kv_read = 0
        while unfinished():
            rounds += 1
            positions = jnp.asarray(np.minimum(pos_np,
                                               self.max_length - 1))
            window_cols = [cur]
            if mtp is not None:
                window_cols.append(proposal)
            else:
                # draft proposal: repair the slot behind cur (idempotent —
                # the K/V write is a pure function of (token, position), and
                # after a fully-accepted window the draft never saw that
                # token), then chain spec_tokens greedy draft steps
                _, dcaches = draft._decode_jit(
                    draft_raw, dcaches, prev,
                    jnp.maximum(positions - 1, 0))
                dcur = cur
                for j in range(self.spec_tokens):
                    dlogits, dcaches = draft._decode_jit(
                        draft_raw, dcaches, dcur,
                        jnp.minimum(positions + j,
                                    self.max_length - 1))
                    dcur = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                    window_cols.append(dcur)
            window = jnp.stack(window_cols, axis=1)  # (B, w)
            live = int((~done).sum())
            t_vf = time.time_ns() if tele else 0
            glogits, *hidden, pools = self._verify_paged_jit(
                raw, self.pool.pools, tables, window, positions, limits)
            self.pool.pools = pools
            kv_read += self._kv_positions_read(
                batch, min(int(pos_np.max()), self.max_length - 1) + w - 1)
            g = np.asarray(jnp.argmax(glogits, axis=-1))  # (B, w) host
            win = np.asarray(window)
            # accept the longest prefix the draft got right: window[j] is
            # committed iff it equals the target's own next token g[j-1]
            match = win[:, 1:] == g[:, :-1]               # (B, w-1)
            m = 1 + np.cumprod(match, axis=1).sum(axis=1)  # (B,) in [1, w]
            accepted_total = 0
            for i in range(b_real):
                if done[i]:
                    continue
                mi = int(m[i])
                accept_num[i] += mi - 1
                accept_den[i] += w - 1
                accepted_total += mi - 1
                for t_new in g[i, :mi]:
                    emitted[i].append(int(t_new))
                    if eos_id is not None and int(t_new) == eos_id:
                        done[i] = True
                        break
                if len(emitted[i]) >= max_new:
                    done[i] = True
            if tele:
                tele.event_deferred(
                    "serving.generate.verify", t_vf, time.time_ns(),
                    batch=batch, window=w, round=rounds,
                    accepted=accepted_total, proposed=live * (w - 1))
            # commit: cur' = g[m-1] at pos+m; prev' = the token at pos+m-1.
            # Rejected positions [pos+m, pos+w) keep reservation; their
            # stale K/V are overwritten before any read (paged.py doc).
            rows = np.arange(batch)
            new_cur = g[rows, np.minimum(m, w) - 1]
            new_prev = np.where(m >= 2, g[rows, np.maximum(m - 2, 0)],
                                np.asarray(cur))
            cur = jnp.asarray(new_cur.astype(np.int32))
            prev = jnp.asarray(new_prev.astype(np.int32))
            if mtp is not None and unfinished():
                # the module follows the target's own picks over the window
                # and proposes from the last position this round committed
                t_md = time.time_ns() if tele else 0
                dlogits, self.pool.pools = self._mtp_draft_jit(
                    raw, mtp.params, self.pool.pools, tables,
                    jnp.asarray(g.astype(np.int32)), hidden[0], positions,
                    limits, jnp.asarray((m - 1).astype(np.int32)))
                proposal = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                if tele:
                    tele.event_deferred(
                        "serving.generate.mtp_draft", t_md, time.time_ns(),
                        batch=batch, window=w, round=rounds)
            pos_np = pos_np + m
        # every round fetched its verify above: launch holds those waits
        tm.phase("serving.generate.wait")
        self._count_kv_read(batch, kv_read, rounds)
        self._count_moe()
        tm.phase("serving.generate.drain")
        if mtp is not None:
            tm.counter("serving.mtp_draft_proposed_total",
                       int(accept_den[:b_real].sum()), model=self.model_id)
            tm.counter("serving.mtp_draft_accepted_total",
                       int(accept_num[:b_real].sum()), model=self.model_id)
        if stats is not None:
            rates = [
                (float(accept_num[i] / accept_den[i])
                 if accept_den[i] else None)
                for i in range(b_real)]
            stats["draft_accept_rate"] = rates
            real = [r for r in rates if r is not None]
            stats["spec_accept_rate"] = (sum(real) / len(real)
                                         if real else None)
            stats["spec_rounds"] = rounds
        return [self._trim_row(emitted[i], max_new, eos_id)
                for i in range(b_real)]

    def _generate_contiguous(self, prompts, max_new_tokens: int, *,
                             temperature: float, key,
                             eos_id: Optional[int], trace: bool):
        """The r13 contiguous-cache engine (``paged=False``) — kept
        verbatim as the paged path's token-identity oracle."""
        tokens, lengths, b_real, lens = self._prep(prompts, max_new_tokens)
        raw = self._raw_params()
        if key is None:
            key = jax.random.PRNGKey(0)
        # deferred span emission (no registry lock in the decode loop —
        # it competes for the GIL with every other model's worker)
        tele = tm.get_telemetry() if trace else None
        batch = int(tokens.shape[0])

        t_pf = time.time_ns() if tele else 0
        logits, caches = self._prefill_jit(raw, tokens, lengths)
        tm.phase("serving.generate.launch")
        if tele:
            tele.event_deferred("serving.generate.prefill", t_pf,
                                time.time_ns(), batch=batch,
                                seq=int(tokens.shape[1]))
        positions = lengths  # where the sampled token goes
        steps = []
        key, sub = jax.random.split(key)
        cur = self._sample(logits, temperature, sub)
        for i in range(max_new_tokens):
            steps.append(cur)
            if i == max_new_tokens - 1:
                break
            logits, caches = self._decode_jit(raw, caches, cur,
                                              positions)
            positions = positions + 1
            key, sub = jax.random.split(key)
            cur = self._sample(logits, temperature, sub)
        tm.phase("serving.generate.wait")
        fetched = [np.asarray(s) for s in steps]
        tm.phase("serving.generate.drain")
        stacked = np.stack(fetched, axis=1)
        return self._trim(stacked, b_real, lens, max_new_tokens, eos_id)

    def generate_full_recompute(self, prompts: Sequence[Sequence[int]],
                                max_new_tokens: int = 16, *,
                                temperature: float = 0.0, key=None,
                                eos_id: Optional[int] = None
                                ) -> List[List[int]]:
        """O(T²) reference decode: re-prefill the whole grown sequence for
        every token. Exactly the same sampling stream as ``generate`` —
        the KV-cache paths (paged AND contiguous) must reproduce it
        token-for-token (greedy) — kept as the verification oracle, not a
        serving path."""
        if max_new_tokens < 1:
            return [[] for _ in prompts]
        grown = [list(p) for p in prompts]
        raw = self._raw_params()
        if key is None:
            key = jax.random.PRNGKey(0)
        steps = []
        for i in range(max_new_tokens):
            tokens, lengths, b_real, _ = self._prep(grown, 1)
            logits, _ = self._oracle_jit(raw, tokens, lengths)
            key, sub = jax.random.split(key)
            cur = self._sample(logits, temperature, sub)
            steps.append(cur)
            host = np.asarray(cur)
            for r in range(len(grown)):
                grown[r].append(int(host[r]))
        stacked = np.stack([np.asarray(s) for s in steps], axis=1)
        lens = [len(p) for p in prompts]
        return self._trim(stacked, len(prompts), lens, max_new_tokens,
                          eos_id)

    # -------------------------------------------------------------- health
    def health_probe(self) -> bool:
        """Finite-logits canary for the reload pipeline
        (docs/SERVING.md#resilience): one tiny prompt through the prefill
        executable; True iff every logit is finite. Runs at an
        already-warmed (smallest-bucket) signature, so on a warmed
        generator it never traces. The paged probe uses an all-trash page
        table — zero blocks reserved, the prompt attention never reads the
        pool — and first audits block-refcount CONSERVATION (plus trie
        consistency when the prefix cache is on), so a leak or
        double-free shows up in steady state, not at the next OOM."""
        b = int(self.policy.bucket_batch(1))
        t = self._prefill_len(1)
        tokens = jnp.ones((b, t), jnp.int32)
        lengths = jnp.ones((b,), jnp.int32)
        raw = self._raw_params()
        if self.paged:
            ok, detail = self.pool.conservation()
            if ok and self.cache is not None:
                # strict when idle: with no live streams the trie's holds
                # are the only legitimate holds, so any other allocated
                # block is a leaked stream ref
                ok, detail = self.cache.check(
                    strict_idle=(self.pool._streams == 0))
            check = ("serving.kv_pool_conservation"
                     + (f".{self.model_id}" if self.model_id else ""))
            tm.set_health(check, ok, detail)
            if not ok:
                return False
            logits, _ = self._launch_prefill(raw, tokens, lengths,
                                             self._trash_address(b))
        else:
            logits, _ = self._prefill_jit(raw, tokens, lengths)
        return bool(np.isfinite(np.asarray(logits)).all())

    # -------------------------------------------------------------- warmup
    def warmup(self, batch_sizes=None, prompt_lengths=None) -> int:
        """Pre-trace every (batch bucket × prefill bucket) prefill, every
        batch-bucket decode step, and — when speculating — every
        batch-bucket verify window and the draft's own programs (a draft
        net's, or a self-draft's two: behind the prefill, behind the
        window), so steady-state serving never compiles (docs/SERVING.md). Defaults to
        the explicit bucket lists of the policy. Returns the number of
        signatures primed."""
        if batch_sizes is None:
            if not isinstance(self.policy.batch_buckets, tuple):
                raise ValueError("warmup() without batch_sizes needs "
                                 "explicit batch buckets")
            batch_sizes = self.policy.batch_buckets
        if prompt_lengths is None:
            if isinstance(self.policy.seq_buckets, tuple):
                # max_length is the implicit final bucket (_prefill_len)
                prompt_lengths = tuple(self.policy.seq_buckets) \
                    + (self.max_length,)
            else:
                # pow2 (the default policy): every pow2 prefill shape up to
                # max_length — log2(L) signatures, so router.load(kind=
                # "generate") on a conf without seq_buckets still boots
                prompt_lengths = tuple(
                    2 ** i for i in range(self.max_length.bit_length())
                ) + (self.max_length,)
        raw = self._raw_params()
        primed = 0
        # resume/chunk windows trace per (batch bucket, width): width is
        # the fixed chunk when configured, else the seq buckets (the
        # suffix-only window goes through the same bucketing)
        window = self.paged and (self.cache is not None
                                 or self.prefill_chunk is not None)
        if window and self.prefill_chunk is not None:
            window_widths = (min(self.prefill_chunk, self.max_length),)
        for b in batch_sizes:
            b = int(b)
            caches = None
            if self.paged:
                tables = self._trash_address(b)
            widths = sorted({min(int(t), self.max_length)
                             for t in prompt_lengths})
            for t in widths:
                tokens = jnp.zeros((b, t), jnp.int32)
                lengths = jnp.ones((b,), jnp.int32)
                if self.paged:
                    _, hidden = self._launch_prefill(raw, tokens, lengths,
                                                     tables)
                    if self.mtp is not None:
                        _, self.pool.pools = self._mtp_prefill_jit(
                            raw, self.mtp.params, self.pool.pools, tokens,
                            lengths, tables, hidden,
                            jnp.zeros((b,), jnp.int32))
                        primed += 1
                else:
                    _, caches = self._prefill_jit(raw, tokens, lengths)
                primed += 1
            if window:
                for t in (window_widths if self.prefill_chunk is not None
                          else widths):
                    zi = jnp.zeros((b, t), jnp.int32)
                    z1 = jnp.zeros((b,), jnp.int32)
                    _, pools = self._prefill_window_jit(
                        raw, self.pool.pools, zi, zi, tables, z1, z1)
                    self.pool.pools = pools
                    primed += 1
            cur = jnp.zeros((b,), jnp.int32)
            pos = jnp.ones((b,), jnp.int32)
            if self.paged:
                limits = jnp.full((b,), self.max_length - 1, jnp.int32)
                _, pools = self._decode_paged_jit(
                    raw, self.pool.pools, tables, cur, pos, limits)
                self.pool.pools = pools
                primed += 1
                if (self.draft is not None or self.mtp is not None) \
                        and self.spec_tokens > 0:
                    vwin = jnp.zeros((b, self.spec_tokens + 1), jnp.int32)
                    _, *hidden, pools = self._verify_paged_jit(
                        raw, self.pool.pools, tables, vwin, pos, limits)
                    self.pool.pools = pools
                    primed += 1
                    if self.mtp is not None:
                        _, self.pool.pools = self._mtp_draft_jit(
                            raw, self.mtp.params, self.pool.pools, tables,
                            vwin, hidden[0], pos, limits, cur)
                        primed += 1
            elif caches is not None:
                self._decode_jit(raw, caches, cur, pos)
                primed += 1
        if window:
            # the COW copy program: block ids are data, one signature ever
            z = jnp.asarray(0, jnp.int32)
            self.pool.pools = self._copy_block_jit(self.pool.pools, z, z)
            primed += 1
        if self.draft is not None:
            primed += self.draft.warmup(batch_sizes=batch_sizes,
                                        prompt_lengths=prompt_lengths)
        return primed

    # ---------------------------------------------------------------- stats
    def pool_stats(self) -> Optional[dict]:
        if self.pool is None:
            return None
        s = self.pool.stats()
        # share of the declared max_length the decode steps read so far
        s["decode_kv_read_share"] = (
            round(self._kv_read / self._kv_declared, 4)
            if self._kv_declared else None)
        share = lambda part, of: round(part / of, 4) if of else None
        for walk, (held, declared, moved, rows) in self._walked.items():
            # share of the declared units the prefills walked so far
            # (kda_prefill_chunk_share, ssm_prefill_position_share)
            s[f"{walk.name}_prefill_{walk.unit[:-1]}_share"] = share(
                held, declared)
            # share of the bucket's rows whose state the decode steps moved
            s[f"{walk.name}_decode_state_share"] = share(moved, rows)
        if self.cache is not None:
            s["prefix_cache"] = self.cache.stats()
        if self.prefill_chunk is not None:
            s["prefill_chunk"] = self.prefill_chunk
        s["recurrent"] = self.recurrent
        s["self_draft"] = self.mtp is not None
        return s

    def prefix_hit_rate(self) -> Optional[float]:
        """Lifetime radix-cache token hit rate, or None when the prefix
        cache is off. Surfaced top-level in ``ServingModel.describe()`` so
        the fleet router (serving/fleet.py) reads it from ``/v1/models``
        without digging through the pool stats tree."""
        if self.cache is None:
            return None
        return round(self.cache.hit_rate(), 4)
