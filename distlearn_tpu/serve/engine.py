"""Slot-addressed decode engine — ``greedy_generate``'s prefill/decode
internals refactored for continuous batching (Orca, Yu et al. OSDI '22).

:func:`distlearn_tpu.models.transformer.greedy_generate` fuses prefill +
decode into one program over one batch that lives and dies together.  A
SERVICE can't do that: requests arrive and finish at different times, so
the engine splits the two phases into separately compiled programs over
a persistent paged K/V pool (:mod:`distlearn_tpu.serve.kv_cache`):

* :meth:`DecodeEngine.admit` runs the PREFILL program for one request —
  a full causal pass over its (bucket-padded) prompt whose K/V scatter
  lands in the slot's pages — and returns the first generated token.
* :meth:`DecodeEngine.tick` runs the DECODE program: every active slot
  advances one token in a single dispatch, each slot gathering its own
  K/V through its block-table row.  A request admitted between ticks
  prefills into slot k while the other slots' cached state just sits in
  the pool — nothing is recomputed or rolled back.

Both programs are built from the SAME block math as training and
``greedy_generate`` (``attn_qkv`` / ``attn_out`` / ``ffn_apply`` /
``decode_attend``), so continuous-batched decoding is token-identical
to N isolated ``greedy_generate`` calls — a tier-1-tested invariant
(tests/test_serve.py).

Tensor parallelism: pass ``mesh``/``tp_axis`` and both programs wrap
their body in ``shard_map`` inside ``jax.jit`` (the mesh-wrapped compile
pattern): weights shard per ``param_specs``, the K/V pools shard over
the heads axis, and ``attn_out``/``ffn_apply`` insert the two psums per
block exactly as the training step does.
"""

from __future__ import annotations

import time
from typing import Any

import numpy as np

from distlearn_tpu import obs
from distlearn_tpu.models.transformer import (_rmsnorm, attn_out, attn_qkv,
                                              decode_attend, ffn_apply,
                                              generate_params, param_specs)
from distlearn_tpu.serve.kv_cache import CacheFull, PagedKVCache

PyTree = Any

__all__ = ["DecodeEngine", "CacheFull", "PrefillJob"]


def _sample_token(jax, jnp, lg, temp, tk, tp_, seed, position):
    """Sample one token from a ``[V]`` float32 logits row.

    ``temp == 0`` returns the plain argmax — the SAME expression the
    greedy path always computed, selected by ``where``, so greedy
    decoding stays bitwise-identical with sampling compiled in.
    ``temp > 0`` draws from the temperature-scaled distribution after
    top-k (``tk > 0``) and nucleus top-p (``0 < tp_``) filtering; the
    key is ``fold_in(PRNGKey(seed), position)`` where ``position`` is
    the sequence position the sampled token will occupy — the draw
    depends only on (seed, position), never on batch composition, cache
    hits, or chunking, so a request replays identically anywhere."""
    V = lg.shape[-1]
    greedy = jnp.argmax(lg).astype(jnp.int32)
    scaled = lg / jnp.where(temp > 0, temp, 1.0).astype(jnp.float32)
    srt = jnp.sort(scaled)[::-1]
    kk = jnp.clip(jnp.where(tk > 0, tk, V), 1, V)
    k_thr = srt[kk - 1]
    probs = jax.nn.softmax(srt)
    # keep a sorted token while the mass STRICTLY BEFORE it is < top_p:
    # the head token always survives, so the filter never empties.
    keep = (jnp.cumsum(probs) - probs) < jnp.where(tp_ > 0, tp_, 1.0)
    p_thr = jnp.min(jnp.where(keep, srt, jnp.inf))
    filt = jnp.where(scaled >= jnp.maximum(k_thr, p_thr), scaled,
                     -jnp.inf)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), position)
    samp = jax.random.categorical(key, filt).astype(jnp.int32)
    return jnp.where(temp > 0, samp, greedy)


class PrefillJob:
    """Resumable prefill state for one admitted request: the slot, the
    prompt, and the next position to prefill (``pos`` starts at the
    prefix-cache ``cached`` length).  Drive with
    :meth:`DecodeEngine.prefill_step` until ``done``; ``first`` then
    holds the request's first generated token."""

    __slots__ = ("slot", "prompt", "pos", "cached", "done", "first")

    def __init__(self, slot: int, prompt: np.ndarray, cached: int):
        self.slot = slot
        self.prompt = prompt
        self.pos = int(cached)
        self.cached = int(cached)
        self.done = False
        self.first: int | None = None


def _buckets(max_len: int) -> tuple[int, ...]:
    """Prompt-length compile buckets: powers of two up to ``max_len``
    (inclusive as the last bucket) — prompts pad up to the next bucket
    so the prefill program retraces O(log max_len) times, not once per
    distinct prompt length."""
    out = []
    b = 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


class DecodeEngine:
    """Continuous-batching decode engine over a fixed-slot paged cache.

    ``params`` is a dense :func:`transformer_lm` tree (per-block or
    scanned layout; MoE rejected).  ``num_slots`` bounds concurrent
    requests; ``max_len`` bounds ``prompt + generated`` per request and
    sizes the page pool (every slot can hold a full-length request).
    """

    def __init__(self, params: PyTree, *, num_slots: int = 4,
                 max_len: int | None = None, page: int = 16,
                 compute_dtype=None, mesh=None, tp_axis: str | None = None,
                 donate: bool = True, spec_k: int = 4,
                 num_pages: int | None = None):
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp
        params, self.depth = generate_params(params)
        self.params = params
        self.cd = compute_dtype or params["embed"].dtype
        self.max_len = int(max_len or params["pos"].shape[0])
        if self.max_len > params["pos"].shape[0]:
            raise ValueError(f"max_len={self.max_len} exceeds the model's "
                             f"positional table {params['pos'].shape[0]}")
        wq = params["block0"]["wq"]
        self.heads, self.head_dim = wq.shape[1], wq.shape[2]
        if (mesh is None) != (tp_axis is None):
            raise ValueError("mesh and tp_axis come together (both or "
                             "neither)")
        if tp_axis is not None and self.heads % mesh.shape[tp_axis]:
            raise ValueError(
                f"{self.heads} heads not divisible by the {tp_axis} axis "
                f"({mesh.shape[tp_axis]})")
        self.mesh, self.tp_axis = mesh, tp_axis
        if spec_k < 1:
            raise ValueError(f"spec_k={spec_k} must be >= 1")
        self.spec_k = int(spec_k)
        self.cache = PagedKVCache(num_slots, page, self.max_len,
                                  num_pages=num_pages)
        self.buckets = _buckets(self.max_len)
        # per-slot sampling state (set at begin/admit): temp == 0 means
        # greedy; fixed dtypes so the tick signature never drifts (DL207)
        self._temp = np.zeros((num_slots,), np.float32)
        self._topk = np.zeros((num_slots,), np.int32)
        self._topp = np.zeros((num_slots,), np.float32)
        self._seed = np.zeros((num_slots,), np.int32)
        shape = (self.depth, self.cache.num_pages, page,
                 self.heads, self.head_dim)
        self._k = jnp.zeros(shape, self.cd)
        self._v = jnp.zeros(shape, self.cd)
        if mesh is not None:
            from jax.sharding import NamedSharding
            self._kv_spec = self._pspec(None, None, None, tp_axis)
            sh = NamedSharding(mesh, self._kv_spec)
            self._k = jax.device_put(self._k, sh)
            self._v = jax.device_put(self._v, sh)
        self._tick_fn = self._build_tick(donate)
        self._prefill_fn = self._build_prefill(donate)
        self._chunk_fn = self._build_chunk(donate)
        self._verify_fn = self._build_verify(donate)
        self._m_ticks = obs.counter("serve_engine_ticks_total",
                                    "decode ticks dispatched")
        self._m_prefills = obs.counter("serve_engine_prefills_total",
                                       "prefill programs dispatched")
        self._m_chunks = obs.counter("serve_engine_prefill_chunks_total",
                                     "resumable prefill chunks dispatched")
        self._m_verifies = obs.counter("serve_engine_verifies_total",
                                       "speculative verify ticks dispatched")
        self._h_tick = obs.histogram("serve_tick_seconds",
                                     "one decode tick: dispatch to tokens "
                                     "on host")
        self._h_accept = obs.histogram(
            "serve_spec_accepted_tokens",
            "tokens emitted per slot per verify tick (accepted drafts + "
            "the bonus token; 1 == plain-tick throughput)",
            buckets=(1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0))

    # -- program construction ----------------------------------------------
    def _pspec(self, *names):
        from jax.sharding import PartitionSpec as P
        return P(*names)

    def _map(self, body, in_specs, out_specs):
        """shard_map(body) under TP, the body itself otherwise — the
        mesh is captured at build time so callers never need a mesh
        context.  Sampling stays OUTSIDE the mapped region (see
        ``_build_tick``): the builders compose it around this."""
        if self.mesh is None:
            return body
        return self._jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False)

    def _wrap(self, body, in_specs, out_specs, donate):
        """jit(shard_map(body)) under TP, plain jit otherwise."""
        jax = self._jax
        return jax.jit(self._map(body, in_specs, out_specs),
                       donate_argnums=(1, 2) if donate else ())

    def _build_tick(self, donate):
        jax, jnp = self._jax, self._jnp
        params, depth, cd, tp = self.params, self.depth, self.cd, self.tp_axis
        page = self.cache.page
        T = self.cache.pages_per_slot * page

        def tick_core(p, kpool, vpool, bt, lens, toks, active):
            S = toks.shape[0]
            pos = lens                                    # position written
            x = p["embed"][toks].astype(cd)[:, None]      # [S,1,E]
            x = x + p["pos"][pos].astype(cd)[:, None]
            # inactive slots write to the trash page (their block-table
            # rows are all 0 already, but pos//page could index past the
            # row for a stale pos — clamp through where)
            row = jnp.clip(pos // page, 0, bt.shape[1] - 1)
            pages = jnp.where(active, bt[jnp.arange(S), row], 0)
            offs = jnp.where(active, pos % page, 0)
            for i in range(depth):
                blk = p[f"block{i}"]
                q, k1, v1 = attn_qkv(blk, x, cd, tp)      # [S,1,H,D]
                kpool = kpool.at[i, pages, offs].set(k1[:, 0])
                vpool = vpool.at[i, pages, offs].set(v1[:, 0])
                # paged gather: each slot's block-table row pulls its
                # pages from the pool -> a contiguous [S,T,H,D] view
                ck = kpool[i][bt].reshape(S, T, k1.shape[2], k1.shape[3])
                cv = vpool[i][bt].reshape(S, T, v1.shape[2], v1.shape[3])
                live = (jnp.arange(T)[None] <= pos[:, None])[:, None, None]
                x = attn_out(blk, x, decode_attend(q, ck, cv, live, cd),
                             cd, tp)
                x = ffn_apply(blk, x, cd, tp_axis=tp)
            x = _rmsnorm(p["out_norm"], x)
            lg = (x[:, 0] @ p["embed"].T.astype(cd)).astype(jnp.float32)
            return kpool, vpool, lg

        P_ = self._pspec
        specs_in = (param_specs(params, self.tp_axis), self._kv_spec,
                    self._kv_spec, P_(), P_(), P_(), P_()) \
            if self.mesh is not None else None
        specs_out = (self._kv_spec, self._kv_spec, P_()) \
            if self.mesh is not None else None
        core = self._map(tick_core, specs_in, specs_out)

        # sampling runs OUTSIDE the mapped region: the logits leave the
        # tp psum replicated, so every device draws the identical token
        # — and the PRNG key is consumed at the single-logical-device
        # level, never inside SPMD with a replicated key (DL003).
        def tick(p, kpool, vpool, bt, lens, toks, active,
                 temp, topk, topp, seed):
            kpool, vpool, lg = core(p, kpool, vpool, bt, lens, toks,
                                    active)
            # the sampled token occupies position lens + 1 next dispatch
            # — that index keys its draw (see _sample_token)
            nxt = jax.vmap(
                lambda r, t, k_, pp, sd, po:
                _sample_token(jax, jnp, r, t, k_, pp, sd, po))(
                lg, temp, topk, topp, seed, lens + 1)
            return kpool, vpool, nxt

        return jax.jit(tick, donate_argnums=(1, 2) if donate else ())

    def _build_prefill(self, donate):
        jax, jnp = self._jax, self._jnp
        lax = jax.lax
        from distlearn_tpu.parallel.sequence import local_attention
        params, depth, cd, tp = self.params, self.depth, self.cd, self.tp_axis
        page = self.cache.page

        def prefill_core(p, kpool, vpool, btrow, tokens, plen):
            # tokens [1, Pb] RIGHT-padded to the bucket: causal attention
            # means positions < plen never see the garbage tail, and the
            # tail's K/V scatter is routed to the trash page below.
            Pb = tokens.shape[1]
            x = p["embed"][tokens].astype(cd)
            x = x + p["pos"][:Pb].astype(cd)[None]
            posn = jnp.arange(Pb)
            valid = posn < plen
            pages = jnp.where(valid, btrow[posn // page], 0)
            offs = jnp.where(valid, posn % page, 0)
            for i in range(depth):
                blk = p[f"block{i}"]
                q, k, v = attn_qkv(blk, x, cd, tp)
                kpool = kpool.at[i, pages, offs].set(k[0])
                vpool = vpool.at[i, pages, offs].set(v[0])
                att = local_attention(q, k, v, causal=True)
                x = attn_out(blk, x, att, cd, tp)
                x = ffn_apply(blk, x, cd, tp_axis=tp)
            x = _rmsnorm(p["out_norm"], x)
            last = lax.dynamic_index_in_dim(x[0], plen - 1, 0,
                                            keepdims=False)
            lg = (last @ p["embed"].T.astype(cd)).astype(jnp.float32)
            return kpool, vpool, lg

        P_ = self._pspec
        specs_in = (param_specs(params, self.tp_axis), self._kv_spec,
                    self._kv_spec, P_(), P_(), P_()) \
            if self.mesh is not None else None
        specs_out = (self._kv_spec, self._kv_spec, P_()) \
            if self.mesh is not None else None
        core = self._map(prefill_core, specs_in, specs_out)

        def prefill(p, kpool, vpool, btrow, tokens, plen,
                    temp, topk, topp, seed):
            kpool, vpool, lg = core(p, kpool, vpool, btrow, tokens, plen)
            # first generated token occupies position plen; sampling sits
            # outside the mapped region (see _build_tick)
            tok = _sample_token(jax, jnp, lg, temp, topk, topp, seed,
                                plen)
            return kpool, vpool, tok

        return jax.jit(prefill, donate_argnums=(1, 2) if donate else ())

    def _build_chunk(self, donate):
        """Resumable-prefill chunk: the causal pass over prompt positions
        ``[p0, p0 + clen)`` of ONE slot, attending through the slot's
        block-table row into the pool — earlier positions (a cached
        prefix, or chunks already run) are READ from their pages, never
        recomputed.  The full-prompt program (:meth:`_build_prefill`)
        stays the ``p0 == 0`` single-dispatch fast path; this one powers
        prefix-cache resume and decode-interleaved chunking."""
        jax, jnp = self._jax, self._jnp
        lax = jax.lax
        params, depth, cd, tp = self.params, self.depth, self.cd, self.tp_axis
        page = self.cache.page
        T = self.cache.pages_per_slot * page
        L = self.max_len

        def chunk_core(p, kpool, vpool, btrow, tokens, p0, clen):
            # tokens [1, Cb] RIGHT-padded; absolute positions p0 + j.
            Cb = tokens.shape[1]
            j = jnp.arange(Cb)
            posn = p0 + j
            x = p["embed"][tokens].astype(cd)
            x = x + p["pos"][jnp.clip(posn, 0, L - 1)].astype(cd)[None]
            valid = j < clen
            pages = jnp.where(
                valid, btrow[jnp.clip(posn // page, 0,
                                      btrow.shape[0] - 1)], 0)
            offs = jnp.where(valid, posn % page, 0)
            for i in range(depth):
                blk = p[f"block{i}"]
                q, k, v = attn_qkv(blk, x, cd, tp)        # [1,Cb,H,D]
                kpool = kpool.at[i, pages, offs].set(k[0])
                vpool = vpool.at[i, pages, offs].set(v[0])
                ck = kpool[i][btrow].reshape(1, T, k.shape[2], k.shape[3])
                cv = vpool[i][btrow].reshape(1, T, v.shape[2], v.shape[3])
                # query at absolute position p0+j sees cache t <= p0+j:
                # the cached prefix, earlier chunks, and this chunk's own
                # causal prefix (scattered above, same layer)
                live = (jnp.arange(T)[None] <= posn[:, None])[None, None]
                x = attn_out(blk, x, decode_attend(q, ck, cv, live, cd),
                             cd, tp)
                x = ffn_apply(blk, x, cd, tp_axis=tp)
            x = _rmsnorm(p["out_norm"], x)
            last = lax.dynamic_index_in_dim(x[0], clen - 1, 0,
                                            keepdims=False)
            lg = (last @ p["embed"].T.astype(cd)).astype(jnp.float32)
            return kpool, vpool, lg

        P_ = self._pspec
        specs_in = (param_specs(params, self.tp_axis), self._kv_spec,
                    self._kv_spec, P_(), P_(), P_(), P_()) \
            if self.mesh is not None else None
        specs_out = (self._kv_spec, self._kv_spec, P_()) \
            if self.mesh is not None else None
        core = self._map(chunk_core, specs_in, specs_out)

        def chunk(p, kpool, vpool, btrow, tokens, p0, clen,
                  temp, topk, topp, seed):
            kpool, vpool, lg = core(p, kpool, vpool, btrow, tokens, p0,
                                    clen)
            # only the FINAL chunk's output is consumed: the first
            # generated token, occupying position p0 + clen == plen;
            # sampling sits outside the mapped region (see _build_tick)
            tok = _sample_token(jax, jnp, lg, temp, topk, topp, seed,
                                p0 + clen)
            return kpool, vpool, tok

        return jax.jit(chunk, donate_argnums=(1, 2) if donate else ())

    def _build_verify(self, donate):
        """Speculative verify: every participating slot scores K = 1 +
        spec_k positions in one dispatch — lane 0 carries the slot's
        ``last_tok`` (exactly what the plain tick would process), lanes
        1..ndraft carry the drafts.  Output is the model argmax at every
        lane; the host accepts the leading run of drafts matching it
        (greedy equivalence is exact — every emitted token IS the
        argmax at its position).  Rejected lanes scattered K/V past the
        accepted length; that is dead state, not damage: lengths never
        advance over it, attention masks it, later writes overwrite it
        (the implicit-rollback invariant, docs/SERVING.md)."""
        jax, jnp = self._jax, self._jnp
        params, depth, cd, tp = self.params, self.depth, self.cd, self.tp_axis
        page = self.cache.page
        T = self.cache.pages_per_slot * page
        L = self.max_len

        def verify(p, kpool, vpool, bt, lens, toks, active, ndraft):
            S, K = toks.shape
            j = jnp.arange(K)
            pos = lens[:, None] + j[None]                 # [S,K]
            valid = active[:, None] & (j[None] <= ndraft[:, None])
            x = p["embed"][toks].astype(cd)               # [S,K,E]
            x = x + p["pos"][jnp.clip(pos, 0, L - 1)].astype(cd)
            row = jnp.clip(pos // page, 0, bt.shape[1] - 1)
            pages = jnp.where(valid,
                              jnp.take_along_axis(bt, row, axis=1), 0)
            offs = jnp.where(valid, pos % page, 0)
            for i in range(depth):
                blk = p[f"block{i}"]
                q, k, v = attn_qkv(blk, x, cd, tp)        # [S,K,H,D]
                kpool = kpool.at[i, pages, offs].set(k)
                vpool = vpool.at[i, pages, offs].set(v)
                ck = kpool[i][bt].reshape(S, T, k.shape[2], k.shape[3])
                cv = vpool[i][bt].reshape(S, T, v.shape[2], v.shape[3])
                live = (jnp.arange(T)[None, None]
                        <= pos[:, :, None])[:, None]      # [S,1,K,T]
                x = attn_out(blk, x, decode_attend(q, ck, cv, live, cd),
                             cd, tp)
                x = ffn_apply(blk, x, cd, tp_axis=tp)
            x = _rmsnorm(p["out_norm"], x)
            lg = (x @ p["embed"].T.astype(cd)).astype(jnp.float32)
            return kpool, vpool, jnp.argmax(lg, axis=-1).astype(jnp.int32)

        P_ = self._pspec
        specs_in = (param_specs(params, self.tp_axis), self._kv_spec,
                    self._kv_spec, P_(), P_(), P_(), P_(), P_()) \
            if self.mesh is not None else None
        specs_out = (self._kv_spec, self._kv_spec, P_()) \
            if self.mesh is not None else None
        return self._wrap(verify, specs_in, specs_out, donate)

    # -- capacity -----------------------------------------------------------
    def has_capacity(self, prompt_len: int, max_new: int,
                     shared_pages: int = 0) -> bool:
        return self.cache.can_admit(int(prompt_len) + int(max_new),
                                    shared_pages=shared_pages)

    def active_slots(self) -> list[int]:
        return np.flatnonzero(self.cache.active).tolist()

    def bucket_for(self, plen: int) -> int:
        for b in self.buckets:
            if plen <= b:
                return b
        raise ValueError(f"prompt length {plen} exceeds max_len "
                         f"{self.max_len}")

    # -- request lifecycle --------------------------------------------------
    def begin(self, prompt: np.ndarray, max_new: int, *, shared=(),
              temperature: float = 0.0, top_k: int = 0,
              top_p: float = 0.0, seed: int = 0) -> PrefillJob:
        """Claim a slot for ``prompt`` and return a resumable
        :class:`PrefillJob` — no compute happens here.  ``shared`` is a
        list of prefix-cache pages covering the prompt's leading whole
        pages (installed by reference; the job prefills only the
        suffix).  Sampling knobs are per-request: ``temperature == 0``
        (default) is exact greedy.  Raises :class:`CacheFull` when no
        slot/pages fit and ``ValueError`` for an impossible request."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = len(prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new={max_new} must be >= 1")
        if not 0.0 <= float(temperature):
            raise ValueError(f"temperature={temperature} must be >= 0")
        if not 0.0 <= float(top_p) <= 1.0:
            raise ValueError(f"top_p={top_p} outside [0, 1]")
        total = plen + int(max_new)
        if total > self.max_len:
            raise ValueError(f"prompt({plen}) + max_new({max_new}) = "
                             f"{total} exceeds max_len {self.max_len}")
        shared = [int(p) for p in shared]
        cached = len(shared) * self.cache.page
        if cached >= plen:
            raise ValueError(f"{len(shared)} shared pages cover the whole "
                             f"{plen}-token prompt — at least the last "
                             "position must prefill (it makes the logits)")
        slot = self.cache.admit(total, shared=shared)
        self._temp[slot] = float(temperature)
        self._topk[slot] = int(top_k)
        self._topp[slot] = float(top_p)
        self._seed[slot] = int(seed)
        return PrefillJob(slot, prompt, cached)

    def prefill_step(self, job: PrefillJob,
                     chunk: int | None = None) -> int | None:
        """Run ONE compiled prefill dispatch for ``job`` — at most
        ``chunk`` prompt positions (whole remainder when ``None``) —
        and return the first generated token once the prompt is fully
        prefilled (``job.done``), else ``None``.  An uncached job with
        no chunk bound takes the original single-dispatch full-prompt
        program (the bitwise-parity path); resumed or chunked jobs go
        through the chunk program."""
        if job.done:
            raise ValueError("prefill_step on a finished job")
        jnp = self._jnp
        plen = len(job.prompt)
        remaining = plen - job.pos
        if job.pos == 0 and (chunk is None or chunk >= plen):
            bucket = self.bucket_for(plen)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = job.prompt
            with obs.span("serve.prefill", slot=job.slot, bucket=bucket):
                self._k, self._v, first = self._prefill_fn(
                    self.params, self._k, self._v,
                    jnp.asarray(self.cache.block_table[job.slot]),
                    jnp.asarray(padded), jnp.int32(plen),
                    jnp.float32(self._temp[job.slot]),
                    jnp.int32(self._topk[job.slot]),
                    jnp.float32(self._topp[job.slot]),
                    jnp.int32(self._seed[job.slot]))
                first = int(first)
            self._m_prefills.inc()
        else:
            take = remaining if chunk is None else min(int(chunk),
                                                       remaining)
            bucket = self.bucket_for(take)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :take] = job.prompt[job.pos:job.pos + take]
            with obs.span("serve.prefill_chunk", slot=job.slot,
                          bucket=bucket, p0=job.pos):
                self._k, self._v, first = self._chunk_fn(
                    self.params, self._k, self._v,
                    jnp.asarray(self.cache.block_table[job.slot]),
                    jnp.asarray(padded), jnp.int32(job.pos),
                    jnp.int32(take),
                    jnp.float32(self._temp[job.slot]),
                    jnp.int32(self._topk[job.slot]),
                    jnp.float32(self._topp[job.slot]),
                    jnp.int32(self._seed[job.slot]))
            self._m_chunks.inc()
            job.pos += take
            if job.pos < plen:
                return None
            first = int(first)
        job.pos = plen
        job.done = True
        job.first = first
        self.cache.lengths[job.slot] = plen
        self.cache.last_tok[job.slot] = first
        return first

    def abort_prefill(self, job: PrefillJob):
        """Release a job that will never finish (deadline/cancel
        mid-prefill): frees the slot and drops its page references."""
        job.done = True
        self.cache.release(job.slot)

    def admit(self, prompt: np.ndarray, max_new: int,
              **kw) -> tuple[int, int]:
        """Prefill ``prompt`` (1-D int array) into a free slot in one
        call; returns ``(slot, first_token)``.  The non-resumable
        wrapper over :meth:`begin` + :meth:`prefill_step`; keyword
        options pass through to :meth:`begin`."""
        job = self.begin(prompt, max_new, **kw)
        first = self.prefill_step(job)
        while first is None:            # cached prefix -> chunk resume
            first = self.prefill_step(job)
        return job.slot, first

    def tick(self, include=None) -> dict[int, int]:
        """Advance every active slot one token in ONE dispatch; returns
        ``{slot: next_token}``.  Slots whose cache allocation is spent
        (``length == limit``) are skipped — the scheduler should have
        finished them; skipping keeps a late finish from scattering past
        the slot's pages.  ``include`` (a slot list) restricts the
        advance to a subset — the scheduler's split when some slots went
        through a speculative verify dispatch this round instead.
        Slots mid-prefill (active with ``length == 0``) are not runnable:
        they have no last token to feed the tick yet."""
        jnp = self._jnp
        c = self.cache
        runnable = c.active & (c.lengths > 0) & (c.lengths < c.limit)
        if include is not None:
            sel = np.zeros((c.num_slots,), bool)
            sel[[int(s) for s in include]] = True
            runnable = runnable & sel
        if not runnable.any():
            return {}
        t0 = time.perf_counter()
        with obs.span("serve.tick", slots=int(runnable.sum())):
            self._k, self._v, nxt = self._tick_fn(
                self.params, self._k, self._v,
                jnp.asarray(c.block_table), jnp.asarray(c.lengths),
                jnp.asarray(c.last_tok), jnp.asarray(runnable),
                jnp.asarray(self._temp), jnp.asarray(self._topk),
                jnp.asarray(self._topp), jnp.asarray(self._seed))
            nxt = np.asarray(nxt)
        self._h_tick.observe(time.perf_counter() - t0)
        self._m_ticks.inc()
        out = {}
        for slot in np.flatnonzero(runnable):
            slot = int(slot)
            c.lengths[slot] += 1            # last_tok's K/V is now cached
            c.last_tok[slot] = int(nxt[slot])
            out[slot] = int(nxt[slot])
        return out

    def verify(self, drafts: dict[int, list]) -> dict[int, list[int]]:
        """Speculative advance: one batched verify dispatch over the
        ``drafts`` slots (slot -> proposed next tokens, possibly empty)
        returning ``{slot: emitted tokens}`` — the leading drafts that
        matched the model's argmax plus the model's own token at the
        first mismatch (1..len(drafts)+1 tokens, never 0: with every
        draft rejected the slot still advances exactly like a plain
        tick).  Greedy slots only; drafts are clipped to ``spec_k`` and
        to the slot's remaining page allocation."""
        jnp = self._jnp
        c = self.cache
        K = self.spec_k + 1
        toks = np.zeros((c.num_slots, K), np.int32)
        nd = np.zeros((c.num_slots,), np.int32)
        part = np.zeros((c.num_slots,), bool)
        for slot, d in drafts.items():
            slot = int(slot)
            if not (c.active[slot] and 0 < c.lengths[slot]
                    < c.limit[slot]):
                continue
            room = int(c.limit[slot]) - int(c.lengths[slot]) - 1
            d = [int(t) for t in d][:min(self.spec_k, max(0, room))]
            part[slot] = True
            nd[slot] = len(d)
            toks[slot, 0] = c.last_tok[slot]
            if d:
                toks[slot, 1:1 + len(d)] = d
        if not part.any():
            return {}
        t0 = time.perf_counter()
        with obs.span("serve.verify", slots=int(part.sum()),
                      drafted=int(nd.sum())):
            self._k, self._v, out = self._verify_fn(
                self.params, self._k, self._v,
                jnp.asarray(c.block_table), jnp.asarray(c.lengths),
                jnp.asarray(toks), jnp.asarray(part), jnp.asarray(nd))
            out = np.asarray(out)
        self._h_tick.observe(time.perf_counter() - t0)
        self._m_verifies.inc()
        res: dict[int, list[int]] = {}
        for slot in np.flatnonzero(part):
            slot = int(slot)
            k = int(nd[slot])
            row = out[slot]
            acc = 0                 # leading drafts matching the argmax
            while acc < k and int(row[acc]) == int(toks[slot, acc + 1]):
                acc += 1
            emitted = [int(t) for t in toks[slot, 1:1 + acc]]
            emitted.append(int(row[acc]))   # bonus: argmax after prefix
            c.lengths[slot] += acc + 1
            c.last_tok[slot] = emitted[-1]
            self._h_accept.observe(float(acc + 1))
            res[slot] = emitted
        return res

    def finish(self, slot: int):
        """Release the slot's pages (request done or evicted)."""
        self.cache.release(slot)

    def swap_params(self, params: PyTree) -> None:
        """Hot-swap the served weights between ticks (the zero-downtime
        deployment path — ``serve.server`` fences admissions around the
        call).  The compiled tick/prefill programs take ``params`` as
        argument 0 and close over nothing, so replacing the tree is
        visible on the next dispatch with NO retrace — provided the new
        tree matches the compiled signature exactly; structure, shape
        and dtype are validated here so a layout drift fails the swap,
        not the next request."""
        jax, jnp = self._jax, self._jnp
        new, depth = generate_params(params)
        if depth != self.depth:
            raise ValueError(f"swap depth {depth} != engine depth "
                             f"{self.depth}")
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new)
        if old_def != new_def:
            raise ValueError("swap param tree structure differs from the "
                             "compiled one")
        for o, n in zip(old_leaves, new_leaves):
            if tuple(o.shape) != tuple(n.shape) or o.dtype != n.dtype:
                raise ValueError(
                    f"swap leaf mismatch: {n.shape}/{n.dtype} where the "
                    f"engine compiled {o.shape}/{o.dtype}")
        self.params = jax.tree_util.tree_map(jnp.asarray, new)

    # -- lint/bench hooks ---------------------------------------------------
    def tick_args(self):
        """Abstract args for the decode-tick program (distlint's cost
        pass compiles the identical program the service runs)."""
        jax, c = self._jax, self.cache
        sd = jax.ShapeDtypeStruct
        kv = sd(self._k.shape, self._k.dtype)
        return (self.params, kv, kv,
                sd(c.block_table.shape, "int32"),
                sd(c.lengths.shape, "int32"),
                sd(c.last_tok.shape, "int32"),
                sd(c.active.shape, "bool"),
                sd((c.num_slots,), "float32"),
                sd((c.num_slots,), "int32"),
                sd((c.num_slots,), "float32"),
                sd((c.num_slots,), "int32"))

    def _sampling_scalar_args(self, sd):
        return (sd((), "float32"), sd((), "int32"),
                sd((), "float32"), sd((), "int32"))

    def prefill_args(self, bucket: int | None = None):
        jax, c = self._jax, self.cache
        sd = jax.ShapeDtypeStruct
        kv = sd(self._k.shape, self._k.dtype)
        b = bucket or self.buckets[0]
        return (self.params, kv, kv,
                sd((c.pages_per_slot,), "int32"),
                sd((1, b), "int32"), sd((), "int32"),
                *self._sampling_scalar_args(sd))

    def chunk_args(self, bucket: int | None = None):
        """Abstract args for one resumable-prefill chunk lowering."""
        jax, c = self._jax, self.cache
        sd = jax.ShapeDtypeStruct
        kv = sd(self._k.shape, self._k.dtype)
        b = bucket or self.buckets[0]
        return (self.params, kv, kv,
                sd((c.pages_per_slot,), "int32"),
                sd((1, b), "int32"), sd((), "int32"), sd((), "int32"),
                *self._sampling_scalar_args(sd))

    def verify_args(self):
        """Abstract args for the speculative verify program."""
        jax, c = self._jax, self.cache
        sd = jax.ShapeDtypeStruct
        kv = sd(self._k.shape, self._k.dtype)
        return (self.params, kv, kv,
                sd(c.block_table.shape, "int32"),
                sd(c.lengths.shape, "int32"),
                sd((c.num_slots, self.spec_k + 1), "int32"),
                sd(c.active.shape, "bool"),
                sd((c.num_slots,), "int32"))

    @property
    def tick_program(self):
        return self._tick_fn

    @property
    def prefill_program(self):
        return self._prefill_fn

    @property
    def chunk_program(self):
        return self._chunk_fn

    @property
    def verify_program(self):
        return self._verify_fn
