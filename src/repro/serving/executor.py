"""StageExecutor: shared compile-reuse prefill/decode execution.

One instance serves one pipeline stage (all replicas of the stage share it,
and therefore share its jit cache) or the whole model as a single stage
(``ServeEngine``). It owns the three compute paths of the generative data
plane:

* :meth:`score`   — stateless teacher-forced forward (legacy submit path)
* :meth:`prefill` — build a per-session decode cache from a token history
* :meth:`decode` / :meth:`decode_many` — one autoregressive step for a
  single session, or one fused dispatch over N stacked sessions at
  *heterogeneous* positions (the continuous-batching hot path)

Compile reuse: jit already caches one executable per input shape; the
executor additionally right-pads prefill sequence lengths up to power-of-two
buckets so arbitrary history lengths (which re-prefill after a failure makes
common) hit a small set of executables instead of compiling per length.
Padding is only applied when every group in the stage slice uses a full
(non-ring, non-SSM) cache: causal masking makes right-padding invisible to
real positions there, while ring buffers would evict real keys and SSM
states would integrate the garbage tail.

``decode_many`` batches sessions by stacking their caches along a fresh
leading axis and ``vmap``-ing the single-step stage decode over it — each
session keeps its own position ``t``, so sessions that started at different
times still coalesce into one dispatch (same-``t``-only batching would never
converge once sessions drift).
"""
from __future__ import annotations

import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import DENSE, MOE, ModelConfig
from repro.statexfer.codec import PagedCachePayload, materialize_paged
from . import kvpool
from .envelope import ROLE_BOTH, ROLE_DECODE, ROLE_PREFILL
from .kvpool import PagedCacheHandle, PagePool
from .partition import (
    StageSpec,
    stage_decode,
    stage_forward,
    stage_init_cache,
    stage_params,
    stage_prefill,
    stage_verify,
    split_stages,
)


def _named_jit(name: str, fn):
    """``jax.jit(fn)`` under ``name``, which XLA takes for the program's
    module name: the device trace then says which stage program ran."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


class StageExecutor:
    def __init__(self, cfg: ModelConfig, spec: StageSpec, sparams: Any, *,
                 max_len: int = 256, pad_seq: bool = True,
                 role: str = ROLE_BOTH, paged: bool = False,
                 page_size: int = 16,
                 pool_pages: int | None = None) -> None:
        self.cfg = cfg
        self.spec = spec
        self.sparams = sparams
        self.max_len = max_len
        #: which pool this executor serves: a ``prefill`` executor never
        #: compiles decode buckets, a ``decode`` executor never compiles the
        #: full prefill shape set — warm bootstrap replays only the role's
        #: slice of a peer's shape profile (see :meth:`warm`)
        self.role = role
        groups = [cfg.groups[gi] for gi, _, _ in spec.slices]
        #: every group uses a full (non-ring, non-SSM) attention cache —
        #: gates right-padding here and replay-idempotent snapshot restore
        #: in statexfer (rewriting position t with the same inputs is an
        #: exact no-op only for full caches)
        self.full_cache = all(
            g.kind in (DENSE, MOE) and g.window is None for g in groups)
        #: right-padding is a pure win only for full-cache attention stages
        self.pad_seq = pad_seq and self.full_cache
        #: paged KV mode: prefill installs the session cache into a shared
        #: PagePool and returns a page-table handle; decode_many stacks page
        #: tables instead of whole caches. Gated on full caches (page writes
        #: rely on decode touching exactly slot t) and page-aligned max_len.
        #: The contiguous path stays as the fallback/degrade target.
        self.paged = bool(paged) and self.full_cache \
            and max_len % page_size == 0
        self.page_size = page_size
        self.pool_pages = pool_pages or (4 * (max_len // page_size) + 1)
        self.pool: PagePool | None = None
        self._pool_init_lock = threading.Lock()
        #: flight-event sink (set by the server: FlightRecorder.record)
        self.on_event = None
        self._paged_many = None
        self._paged_widths_seen: set[int] = set()
        #: cached all-zeros donor caches for convoy pad slots, one per
        #: distinct cache leaf signature (built once, reused every pad)
        self._pad_caches: dict = {}
        tokens_in = spec.first
        #: program name prefix: the stage, e.g. ``s1_decode_many``
        self._tag = f"s{spec.index}"

        self._score = _named_jit(
            f"{self._tag}_score",
            lambda sp, x: stage_forward(cfg, spec, sp, x, tokens_in=tokens_in))
        self._prefill = _named_jit(
            f"{self._tag}_prefill",
            lambda sp, x, n=None: stage_prefill(cfg, spec, sp, x, max_len,
                                                tokens_in=tokens_in,
                                                length=n))
        self._decode = _named_jit(
            f"{self._tag}_decode",
            lambda sp, c, x, t: stage_decode(cfg, spec, sp, c, x, t,
                                             tokens_in=tokens_in))
        # N sessions, each with its own cache and position, in one dispatch:
        # vmap over a stacked leading axis keeps every per-session batch dim
        # intact, so the inner stage_decode is byte-for-byte the single path.
        # Stacking N caches and splitting the N results back apart happens
        # INSIDE the jitted function — done on the host it costs dozens of
        # tiny dispatches per fused batch and erases the batching win.
        def _many(sp, caches, xs, ts):
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *caches)
            x = jnp.stack(xs)
            outs, new_stacked = jax.vmap(
                lambda c, xi, ti: stage_decode(cfg, spec, sp, c, xi, ti,
                                               tokens_in=tokens_in),
                in_axes=(0, 0, 0))(stacked, x, ts)
            n = len(caches)
            return (tuple(outs[i] for i in range(n)),
                    tuple(jax.tree.map(lambda l: l[i], new_stacked)
                          for i in range(n)))

        self._decode_many = _named_jit(f"{self._tag}_decode_many", _many)

        # Speculative verification: K stacked tokens per session (the
        # current token plus k draft proposals) integrated in ONE dispatch.
        # Same vmap-over-stacked-caches shape as ``_many``; the inner
        # per-session body is a single teacher-forced K-position sweep
        # (``stage_verify``) on full-cache stages — one weight pass where
        # K sequential decode steps would cost K — with the sequential
        # loop kept as the fallback for ring/SSM cache stages. K is
        # static (read from the input shape), so each (width, K) pair is
        # one fused executable. Last stage emits (B, K, V) logits; hidden
        # stages emit (B, K, D).
        full_cache = self.full_cache

        def _vmany(sp, caches, xs, ts):
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *caches)
            x = jnp.stack(xs)
            k = xs[0].shape[1]

            def one(c, xi, ti):
                if full_cache:
                    return stage_verify(cfg, spec, sp, c, xi, ti,
                                        tokens_in=tokens_in)
                ys = []
                for j in range(k):
                    y, c = stage_decode(cfg, spec, sp, c, xi[:, j:j + 1],
                                        ti + j, tokens_in=tokens_in)
                    ys.append(y)
                out = (jnp.stack(ys, axis=1) if ys[0].ndim == 2
                       else jnp.concatenate(ys, axis=1))
                return out, c

            outs, new_stacked = jax.vmap(one, in_axes=(0, 0, 0))(
                stacked, x, ts)
            n = len(caches)
            return (tuple(outs[i] for i in range(n)),
                    tuple(jax.tree.map(lambda l: l[i], new_stacked)
                          for i in range(n)))

        self._verify_many_fn = _named_jit(f"{self._tag}_verify_many",
                                          _vmany)
        self._paged_verify = None
        #: jitted draft rollouts, one per proposal budget k (the greedy
        #: argmax feedback loop makes k part of the program, not a shape)
        self._propose_fns: dict = {}
        self._propose_shapes_seen: set[tuple] = set()

        self.stats = {"score_calls": 0, "prefill_calls": 0,
                      "decode_batches": 0, "decode_steps": 0,
                      "first_call_compile_s": 0.0, "warmed_dispatches": 0,
                      "paged_decode_batches": 0, "paged_degrades": 0,
                      "verify_batches": 0, "verify_steps": 0,
                      "verify_tokens": 0, "propose_calls": 0,
                      "propose_tokens": 0,
                      # real and bucketed positions prefilled
                      "prefill_tokens": 0, "prefill_positions": 0}
        #: fused convoy widths already compiled (first-dispatch timing)
        self._widths_seen: set[int] = set()
        #: fused verify (width, K) shapes already compiled — part of the
        #: warm profile so bootstrap precompiles verify buckets too
        self._verify_widths_seen: set[tuple] = set()
        self._paged_verify_widths_seen: set[tuple] = set()
        #: post-bucketing prefill input shapes served so far — together with
        #: the widths this is the executor's *warm profile*: exactly the
        #: executables a same-role executor needs compiled (WarmBootstrap)
        self._prefill_shapes_seen: set[tuple] = set()

    @classmethod
    def for_model(cls, model, params, *, max_len: int = 256,
                  pad_seq: bool = True, **kw) -> "StageExecutor":
        """Whole model as a single stage (the standalone-engine case)."""
        spec = split_stages(model.cfg, 1)[0]
        return cls(model.cfg, spec, stage_params(model.cfg, params, spec),
                   max_len=max_len, pad_seq=pad_seq, **kw)

    # ------------------------------------------------------------------ shapes
    @staticmethod
    def _bucket(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    @staticmethod
    def _width_bucket(n: int) -> int:
        b = 2
        while b < n:
            b *= 2
        return b

    def _timed(self, key: str, fn, *args):
        """Record first-dispatch wall time (dominated by jit compile — the
        analogue of the paper's NCCL lazy-init dip) per executor."""
        first = self.stats[key] == 0
        t0 = time.monotonic()
        out = fn(self.sparams, *args)
        if first:
            jax.block_until_ready(out)
            self.stats["first_call_compile_s"] += time.monotonic() - t0
        self.stats[key] += 1
        return out

    # ----------------------------------------------------------------- compute
    def score(self, x: jax.Array) -> jax.Array:
        """Teacher-forced forward: tokens/hidden (B,S[,D]) -> full output."""
        return self._timed("score_calls", self._score, x)

    def prefill_len(self, s: int) -> int:
        """Positions the prefill of ``s`` real ones computes: its bucket,
        where this stage pads."""
        if self.pad_seq:
            return max(s, min(self._bucket(s), self.max_len))
        return s

    def prefill(self, x: jax.Array) -> tuple[jax.Array, Any]:
        """History (B,S[,D]) -> (output, session cache). The output is the
        hidden state sliced back to S positions, or on the last stage the
        logits (B,1,V) of the last real position.

        In paged mode the contiguous prefill result is installed into the
        shared PagePool (leading full pages deduped against the prefix
        trie) and a :class:`~repro.serving.kvpool.PagedCacheHandle` is
        returned instead; on template mismatch or pool exhaustion the
        session simply keeps the contiguous cache."""
        x0, s = x, x.shape[1]
        sp = self.prefill_len(s)
        if sp > s:
            pad = [(0, 0), (0, sp - s)] + [(0, 0)] * (x.ndim - 2)
            x = jnp.pad(x, pad)
        self._prefill_shapes_seen.add((tuple(x.shape), str(x.dtype)))
        out, cache = self._timed("prefill_calls", self._prefill, x,
                                 np.int32(s))
        self.stats["prefill_tokens"] += x.shape[0] * s
        self.stats["prefill_positions"] += x.shape[0] * sp
        if not self.spec.last and out.shape[1] != s:
            out = out[:, :s]
        if self.paged:
            keys = kvpool.prefix_chunk_keys(x0, s, self.page_size)
            handle = self._ensure_pool().install_prefill(cache, s, keys)
            if handle is not None:
                return out, handle
        return out, cache

    def decode(self, cache: Any, x: jax.Array, t) -> tuple[jax.Array, Any]:
        """Single-session step: token/hidden (B,1[,D]) at position ``t``."""
        if isinstance(cache, PagedCacheHandle):
            return self._paged_decode_many([cache], [x], [t])[0]
        out, new_cache = self._timed(
            "decode_steps", self._decode, cache, x, jnp.int32(t))
        self.stats["decode_batches"] += 1
        return out, new_cache

    def decode_many(self, caches: list[Any], xs: list[jax.Array],
                    ts: list[int]) -> list[tuple[jax.Array, Any]]:
        """One fused dispatch over N sessions (own cache + position each).

        All ``xs`` must share one shape (same per-session batch); positions
        are free. Returns per-session (output, new_cache) in input order.
        Paged and contiguous sessions may mix in one convoy: each kind
        dispatches fused with its peers and the results merge in order.

        Convoy widths are bucketed to powers of two by duplicating lane 0's
        input shape (results discarded): otherwise every distinct width
        2..max compiles its own executable mid-serving, a compile stall per
        new width — the decode-path analogue of the prefill sequence
        buckets. Pad slots carry a cached all-zeros donor cache (built once
        per leaf signature), not a stacked copy of a real session's cache.
        """
        paged_idx = [i for i, c in enumerate(caches)
                     if isinstance(c, PagedCacheHandle)]
        if paged_idx:
            results: list = [None] * len(caches)
            contig_idx = [i for i in range(len(caches))
                          if not isinstance(caches[i], PagedCacheHandle)]
            paged_out = self._paged_decode_many(
                [caches[i] for i in paged_idx],
                [xs[i] for i in paged_idx], [ts[i] for i in paged_idx])
            for i, r in zip(paged_idx, paged_out):
                results[i] = r
            if contig_idx:
                contig_out = self.decode_many(
                    [caches[i] for i in contig_idx],
                    [xs[i] for i in contig_idx], [ts[i] for i in contig_idx])
                for i, r in zip(contig_idx, contig_out):
                    results[i] = r
            return results
        n = len(caches)
        if n == 1:
            return [self.decode(caches[0], xs[0], ts[0])]
        width = self._width_bucket(n)
        if width > n:
            pad = width - n
            caches = list(caches) + [self._pad_cache(caches[0])] * pad
            xs = list(xs) + [xs[0]] * pad
            ts = list(ts) + [0] * pad
        t = jnp.asarray(ts, jnp.int32)
        first = width not in self._widths_seen
        self._widths_seen.add(width)
        t0 = time.monotonic()
        outs, new_caches = self._decode_many(
            self.sparams, tuple(caches), tuple(xs), t)
        if first:
            jax.block_until_ready(outs)
            self.stats["first_call_compile_s"] += time.monotonic() - t0
        self.stats["decode_batches"] += 1
        self.stats["decode_steps"] += n
        return list(zip(outs[:n], new_caches[:n]))

    def _make_propose(self, k: int):
        cfg, spec, tokens_in = self.cfg, self.spec, self.spec.first
        full_cache = self.full_cache

        def _roll(sp, cache, xs, t):
            c = cache
            p = xs.shape[1]
            # integrate the P pending tokens in one teacher-forced sweep
            # where the cache layout allows it; the k-1 proposal steps
            # after it are inherently sequential (argmax feedback)
            if full_cache:
                y, c = stage_verify(cfg, spec, sp, c, xs, t,
                                    tokens_in=tokens_in)
                y = y[:, -1]
            else:
                y = None
                for j in range(p):
                    y, c = stage_decode(cfg, spec, sp, c, xs[:, j:j + 1],
                                        t + j, tokens_in=tokens_in)
            tok = jnp.argmax(y, axis=-1).astype(jnp.int32)[:, None]
            props = [tok]
            for i in range(1, k):
                y, c = stage_decode(cfg, spec, sp, c, props[-1],
                                    t + p + i - 1, tokens_in=tokens_in)
                props.append(
                    jnp.argmax(y, axis=-1).astype(jnp.int32)[:, None])
            return jnp.concatenate(props, axis=1), c

        return _named_jit(f"{self._tag}_propose", _roll)

    def propose_rollout(self, cache: Any, xs: jax.Array, t, k: int
                        ) -> tuple[jax.Array, Any]:
        """Draft-side speculative proposal in ONE dispatch.

        Integrates the P pending history tokens ``xs`` (B, P) at positions
        ``t .. t+P-1``, then rolls out ``k`` greedy proposals with argmax
        feedback — the whole integrate+propose loop is jit-fused (one
        executable per (P, k), both small and bounded by the speculation
        budget), so a proposal round costs one dispatch no matter how many
        tokens the last verify committed. Sequential single-token decodes
        here would cost P+k-1 dispatches per round and erase the
        speculative win at small-model scale. Full-model (logits-emitting)
        contiguous executors only — the draft pool never pages and never
        splits across stages. Returns (proposals (B, k) int32, new cache).
        """
        k = int(k)
        fn = self._propose_fns.get(k)
        if fn is None:
            fn = self._make_propose(k)
            self._propose_fns[k] = fn
        xs = jnp.asarray(xs, jnp.int32)
        key = (int(xs.shape[0]), int(xs.shape[1]), k)
        first = key not in self._propose_shapes_seen
        self._propose_shapes_seen.add(key)
        t0 = time.monotonic()
        props, new_cache = fn(self.sparams, cache, xs, jnp.int32(t))
        if first:
            jax.block_until_ready(props)
            self.stats["first_call_compile_s"] += time.monotonic() - t0
        self.stats["propose_calls"] += 1
        self.stats["propose_tokens"] += k
        return props, new_cache

    def _pad_cache(self, like: Any) -> Any:
        """All-zeros donor cache for convoy pad slots, cached per leaf
        signature: padding with ``caches[0]`` stacked a real session's
        cache bytes once per pad lane per microbatch for results nobody
        reads."""
        key = tuple((tuple(leaf.shape), str(leaf.dtype))
                    for leaf in jax.tree.leaves(like))
        donor = self._pad_caches.get(key)
        if donor is None:
            donor = jax.tree.map(jnp.zeros_like, like)
            self._pad_caches[key] = donor
        return donor

    # -------------------------------------------------------- spec. verify
    def verify_many(self, caches: list[Any], xs: list[jax.Array],
                    ts: list[int]) -> list[tuple[jax.Array, Any]]:
        """One fused *speculative verification* dispatch over N sessions.

        Each ``xs[i]`` stacks K tokens (the session's current committed
        token plus its k=K-1 draft proposals) — or K hidden-state columns
        on downstream stages — integrated at positions ``ts[i]..ts[i]+K-1``
        in one executable, exactly like ``decode_many`` but K-deep. The
        last stage returns (B, K, V) logits so the caller can judge the
        accepted prefix token-by-token (greedy parity is exact: position
        j's logits saw precisely the tokens 0..ts[i]+j-1). Rejected-suffix
        cache writes land in slots the decode validity mask never reads;
        paged handles additionally roll trailing pages back via
        :meth:`commit_verify`. Widths bucket to powers of two like decode
        convoys; each (width, K) pair compiles once.
        """
        paged_idx = [i for i, c in enumerate(caches)
                     if isinstance(c, PagedCacheHandle)]
        if paged_idx:
            results: list = [None] * len(caches)
            contig_idx = [i for i in range(len(caches))
                          if not isinstance(caches[i], PagedCacheHandle)]
            paged_out = self._paged_verify_many(
                [caches[i] for i in paged_idx],
                [xs[i] for i in paged_idx], [ts[i] for i in paged_idx])
            for i, r in zip(paged_idx, paged_out):
                results[i] = r
            if contig_idx:
                contig_out = self.verify_many(
                    [caches[i] for i in contig_idx],
                    [xs[i] for i in contig_idx], [ts[i] for i in contig_idx])
                for i, r in zip(contig_idx, contig_out):
                    results[i] = r
            return results
        n = len(caches)
        k = int(xs[0].shape[1])
        width = n if n == 1 else self._width_bucket(n)
        if width > n:
            pad = width - n
            caches = list(caches) + [self._pad_cache(caches[0])] * pad
            xs = list(xs) + [xs[0]] * pad
            ts = list(ts) + [0] * pad
        t = jnp.asarray(ts, jnp.int32)
        first = (width, k) not in self._verify_widths_seen
        self._verify_widths_seen.add((width, k))
        t0 = time.monotonic()
        outs, new_caches = self._verify_many_fn(
            self.sparams, tuple(caches), tuple(xs), t)
        if first:
            jax.block_until_ready(outs)
            self.stats["first_call_compile_s"] += time.monotonic() - t0
        self.stats["verify_batches"] += 1
        self.stats["verify_steps"] += n
        self.stats["verify_tokens"] += n * k
        return list(zip(outs[:n], new_caches[:n]))

    def commit_verify(self, cache: Any, length: int) -> Any:
        """Finalize a session's cache after verification accepted
        ``length`` total tokens (slots ``0..length-1`` live). Contiguous
        caches need nothing — rejected-suffix slots are overwritten before
        any read. Paged handles pop the trailing pages the speculative
        writes grew/COW'd past the accepted prefix (``PagePool.truncate``),
        so a low-acceptance session cannot leak pool occupancy."""
        if isinstance(cache, PagedCacheHandle):
            cache.pool.truncate(cache, int(length))
        return cache

    def _paged_verify_many(self, handles: list, xs: list,
                           ts: list) -> list[tuple[jax.Array, Any]]:
        """Paged speculative verification: prepare all K write slots per
        lane under the pool lock (growth + COW, so every written page is
        lane-exclusive), then one jitted dispatch that gathers each lane's
        cache, runs K decode steps, and scatters back the fixed-size page
        window covering the written slots. Any lane whose upkeep fails
        degrades to a contiguous cache and rides the contiguous verify."""
        n = len(handles)
        k = int(xs[0].shape[1])
        results: list = [None] * n
        caches = list(handles)
        live = []
        degraded = []
        pool = self._ensure_pool()
        # writes span at most W pages; a K too large for the per-seq table
        # window cannot dispatch paged at all
        w_need = (k + pool.page_size - 2) // pool.page_size + 1
        with pool.lock:
            for i, (h, t) in enumerate(zip(handles, ts)):
                ok = (h.pool is self.pool and w_need <= pool.pages_per_seq
                      and int(t) + k <= self.max_len)
                if ok:
                    for j in range(k):
                        if not self.pool.prepare_write(h, int(t) + j):
                            ok = False
                            break
                if ok:
                    live.append(i)
                else:
                    caches[i] = h.pool.materialize(h)
                    h.pool.release(h)
                    self.stats["paged_degrades"] += 1
                    degraded.append(i)
            if live:
                outs = self._dispatch_paged_verify(
                    [caches[i] for i in live], [xs[i] for i in live],
                    [ts[i] for i in live])
                for i, r in zip(live, outs):
                    results[i] = r
        if degraded:
            fallback = self.verify_many([caches[i] for i in degraded],
                                        [xs[i] for i in degraded],
                                        [ts[i] for i in degraded])
            for i, r in zip(degraded, fallback):
                results[i] = r
        return results

    def _dispatch_paged_verify(self, handles: list, xs: list,
                               ts: list) -> list[tuple[jax.Array, Any]]:
        pool = self.pool
        n = len(handles)
        k = int(xs[0].shape[1])
        width = n if n == 1 else self._width_bucket(n)
        tables = np.zeros((width, pool.pages_per_seq), np.int32)
        for i, h in enumerate(handles):
            tables[i, :len(h.pages)] = h.pages
        xs_p = list(xs) + [xs[0]] * (width - n)
        ts_p = list(ts) + [0] * (width - n)
        fn = self._get_paged_verify()
        first = (width, k) not in self._paged_verify_widths_seen
        self._paged_verify_widths_seen.add((width, k))
        t0 = time.monotonic()
        outs, new_leaves = fn(self.sparams, tuple(pool.leaves),
                              jnp.asarray(tables),
                              tuple(xs_p), jnp.asarray(ts_p, jnp.int32))
        if first:
            jax.block_until_ready(outs)
            self.stats["first_call_compile_s"] += time.monotonic() - t0
        pool.leaves = list(new_leaves)
        for h, t in zip(handles, ts):
            h.length = max(h.length, int(t) + k)
        self.stats["verify_batches"] += 1
        self.stats["verify_steps"] += n
        self.stats["verify_tokens"] += n * k
        self.stats["paged_decode_batches"] += 1
        return [(outs[i], handles[i]) for i in range(n)]

    def _get_paged_verify(self):
        if self._paged_verify is None:
            cfg, spec, pool = self.cfg, self.spec, self.pool
            tokens_in = spec.first
            axes = tuple(pool.axes)
            page = pool.page_size
            pps = pool.pages_per_seq
            structure = jax.tree.structure(pool.skeleton)

            def _many_pv(sp, pool_leaves, tables, xs, ts):
                def one(table, x, t):
                    leaves = kvpool.gather_pages(pool_leaves, axes, table,
                                                 page)
                    cache = jax.tree.unflatten(structure, leaves)
                    kk = x.shape[1]
                    # paged executors are full-cache by construction, so
                    # the K positions verify in one teacher-forced sweep
                    out, cache = stage_verify(cfg, spec, sp, cache, x, t,
                                              tokens_in=tokens_in)
                    new_leaves = structure.flatten_up_to(cache)
                    # fixed page window covering every written slot; when
                    # the clamp pulls the window start below t//page the
                    # extra leading pages scatter back bit-identical
                    # gathered content (a value-level no-op even for
                    # shared pages)
                    w = (kk + page - 2) // page + 1
                    li0 = jnp.minimum(t // page, pps - w)
                    pgs = []
                    for leaf, ax in zip(new_leaves, axes):
                        pgs.append(jnp.stack([
                            jax.lax.dynamic_slice_in_dim(
                                leaf, (li0 + wi) * page, page, axis=ax)
                            for wi in range(w)]))
                    phys = jax.lax.dynamic_slice_in_dim(table, li0, w)
                    return out, pgs, phys

                x = jnp.stack(xs)
                outs, pgs, phys = jax.vmap(one, in_axes=(0, 0, 0))(
                    tables, x, ts)
                # written pages are lane-exclusive (prepare_write COW'd
                # them); unwritten window pages rewrite their own bytes;
                # zero table entries and pad lanes land on scratch page 0
                flat_phys = phys.reshape(-1)
                new_pool = tuple(
                    leaf.at[flat_phys].set(
                        pg.reshape((-1,) + pg.shape[2:]))
                    for leaf, pg in zip(pool_leaves, pgs))
                return outs, new_pool

            self._paged_verify = _named_jit(f"{self._tag}_verify_paged",
                                            _many_pv)
        return self._paged_verify

    # ------------------------------------------------------------ paged mode
    def _ensure_pool(self) -> PagePool:
        with self._pool_init_lock:
            if self.pool is None:
                self.pool = PagePool(
                    self.cfg, self.spec, max_len=self.max_len,
                    page_size=self.page_size, num_pages=self.pool_pages,
                    on_event=self._pool_event)
        return self.pool

    def _pool_event(self, kind: str, **fields) -> None:
        if self.on_event is not None:
            self.on_event(kind, **fields)

    def adopt_cache(self, cache: Any) -> Any:
        """Normalize an installed session cache for this executor. Paged
        wire payloads enter the pool directly (page-granular restore, full
        prefix pages re-shared via the trie); without a usable pool they
        materialize to a contiguous cache. Handles and contiguous caches
        pass through."""
        if isinstance(cache, PagedCachePayload):
            if self.paged:
                handle = self._ensure_pool().install_payload(cache)
                if handle is not None:
                    return handle
            return materialize_paged(cache)
        return cache

    def release_cache(self, cache: Any) -> None:
        """Return a dropped session's pool pages (no-op for contiguous)."""
        if isinstance(cache, PagedCacheHandle):
            cache.pool.release(cache)

    def _paged_decode_many(self, handles: list, xs: list,
                           ts: list) -> list[tuple[jax.Array, Any]]:
        """Fused decode over paged sessions: host-side page-table upkeep
        (growth + copy-on-write), then one jitted dispatch that gathers
        each lane's cache through its page table and scatters back only the
        page containing its written slot. A session whose upkeep fails
        (pool exhausted) degrades to a contiguous cache and rides the
        contiguous path — never crashes."""
        n = len(handles)
        results: list = [None] * n
        caches = list(handles)
        live = []
        degraded = []
        # hold the pool lock across upkeep + dispatch + leaves writeback:
        # replicas share this executor and decode on worker threads, and a
        # concurrent dispatch reading the same pool arrays would lose this
        # one's page writes when it stores its own new arrays back
        with self._ensure_pool().lock:
            for i, (h, t) in enumerate(zip(handles, ts)):
                ok = (self.pool is not None and h.pool is self.pool
                      and self.pool.prepare_write(h, int(t)))
                if ok:
                    live.append(i)
                else:
                    caches[i] = h.pool.materialize(h)
                    h.pool.release(h)
                    self.stats["paged_degrades"] += 1
                    degraded.append(i)
            if live:
                outs = self._dispatch_paged([caches[i] for i in live],
                                            [xs[i] for i in live],
                                            [ts[i] for i in live])
                for i, r in zip(live, outs):
                    results[i] = r
        if degraded:
            fallback = self.decode_many([caches[i] for i in degraded],
                                        [xs[i] for i in degraded],
                                        [ts[i] for i in degraded])
            for i, r in zip(degraded, fallback):
                results[i] = r
        return results

    def _dispatch_paged(self, handles: list, xs: list,
                        ts: list) -> list[tuple[jax.Array, Any]]:
        pool = self.pool
        n = len(handles)
        width = n if n == 1 else self._width_bucket(n)
        tables = np.zeros((width, pool.pages_per_seq), np.int32)
        for i, h in enumerate(handles):
            tables[i, :len(h.pages)] = h.pages
        # pad lanes: all-zero tables target the reserved scratch page — the
        # gather reads garbage nobody looks at, the writeback lands on page 0
        xs_p = list(xs) + [xs[0]] * (width - n)
        ts_p = list(ts) + [0] * (width - n)
        fn = self._get_paged_many()
        first = width not in self._paged_widths_seen
        self._paged_widths_seen.add(width)
        t0 = time.monotonic()
        outs, new_leaves = fn(self.sparams, tuple(pool.leaves),
                              jnp.asarray(tables),
                              tuple(xs_p), jnp.asarray(ts_p, jnp.int32))
        if first:
            jax.block_until_ready(outs)
            self.stats["first_call_compile_s"] += time.monotonic() - t0
        pool.leaves = list(new_leaves)
        for h, t in zip(handles, ts):
            h.length = max(h.length, int(t) + 1)
        self.stats["decode_batches"] += 1
        self.stats["decode_steps"] += n
        self.stats["paged_decode_batches"] += 1
        return [(outs[i], handles[i]) for i in range(n)]

    def _get_paged_many(self):
        if self._paged_many is None:
            cfg, spec, pool = self.cfg, self.spec, self.pool
            tokens_in = spec.first
            axes = tuple(pool.axes)
            page = pool.page_size
            structure = jax.tree.structure(pool.skeleton)

            def _many_paged(sp, pool_leaves, tables, xs, ts):
                def one(table, x, t):
                    leaves = kvpool.gather_pages(pool_leaves, axes, table,
                                                 page)
                    cache = jax.tree.unflatten(structure, leaves)
                    out, new_cache = stage_decode(cfg, spec, sp, cache, x, t,
                                                  tokens_in=tokens_in)
                    new_leaves = structure.flatten_up_to(new_cache)
                    li = t // page
                    pg = [jax.lax.dynamic_slice_in_dim(
                        leaf, li * page, page, axis=ax)
                        for leaf, ax in zip(new_leaves, axes)]
                    return out, pg, table[li]

                x = jnp.stack(xs)
                outs, pgs, phys = jax.vmap(one, in_axes=(0, 0, 0))(
                    tables, x, ts)
                # distinct lanes own distinct physical pages (prepare_write
                # guarantees exclusivity); pad lanes all hit scratch page 0
                new_pool = tuple(
                    leaf.at[phys].set(pg)
                    for leaf, pg in zip(pool_leaves, pgs))
                return outs, new_pool

            self._paged_many = _named_jit(f"{self._tag}_decode_paged",
                                          _many_paged)
        return self._paged_many

    # ---------------------------------------------------------- warm profile
    def warm_profile(self) -> dict:
        """What a same-role executor must compile to serve like this one:
        the bucketed prefill shapes served so far and the fused decode
        convoy widths dispatched so far (WarmBootstrap ships this from a
        peer replica to a fresh one)."""
        return {"prefill": sorted(self._prefill_shapes_seen),
                "widths": sorted(self._widths_seen),
                "verify": sorted(self._verify_widths_seen),
                "propose": sorted(self._propose_shapes_seen)}

    def obs_stats(self) -> dict:
        """Flat numeric view of the executor for the metrics export
        surface: dispatch counters plus how much of the jit cache the
        served traffic has populated (warm-profile cardinality)."""
        out = dict(self.stats)
        out["prefill_shapes_compiled"] = len(self._prefill_shapes_seen)
        out["decode_widths_compiled"] = len(self._widths_seen)
        out["paged_widths_compiled"] = len(self._paged_widths_seen)
        out["verify_widths_compiled"] = (len(self._verify_widths_seen)
                                        + len(self._paged_verify_widths_seen))
        out["propose_shapes_compiled"] = len(self._propose_shapes_seen)
        if self.pool is not None:
            out.update(self.pool.stats())
        return out

    def pool_stats(self) -> dict:
        """Page-pool gauges for the kvpool metrics group ({} when the pool
        has not been built — no paged session served yet)."""
        return self.pool.stats() if self.pool is not None else {}

    def warm(self, profile: dict) -> int:
        """Replay a peer's warm profile with dummy inputs so every listed
        executable is compiled before real traffic arrives. Returns the
        number of warm dispatches issued. Dummy results are discarded; the
        dispatches land in the shared jit cache, which is the entire point.

        Role filtering (disaggregated pools): a ``prefill`` executor replays
        only the prefill shape set — its replicas never decode, so compiling
        decode convoy widths would burn warm time on executables the jit
        cache never serves. A ``decode`` executor skips prefill compiles
        entirely: its caches arrive pre-built over the handoff wire, so the
        donor caches for width warmup are constructed host-side with
        :func:`stage_init_cache` (an allocation, not a compile) — one per
        distinct batch shape instead of one prefill executable per sequence
        bucket. Either way the role's warm bootstrap is strictly cheaper
        than the colocated profile replay.
        """
        if self.role == ROLE_DECODE:
            return self._warm_decode_only(profile)
        dispatches = 0
        widths = (list(profile.get("widths", []))
                  if self.role != ROLE_PREFILL else [])
        verifies = (list(profile.get("verify", []))
                    if self.role != ROLE_PREFILL else [])
        proposes = (list(profile.get("propose", []))
                    if self.role != ROLE_PREFILL else [])
        for shape, dtype in profile.get("prefill", []):
            x = jnp.zeros(shape, dtype=jnp.dtype(dtype))
            # go through the jitted callable directly: prefill() would
            # re-bucket (already-bucketed shapes pass through unchanged) and
            # pollute the first-call timing stats
            out, cache = self._prefill(self.sparams, x, np.int32(shape[1]))
            jax.block_until_ready(out)
            self._prefill_shapes_seen.add((tuple(shape), str(dtype)))
            dispatches += 1
            if self.role == ROLE_PREFILL:
                continue
            # decode warmup needs a live cache of the right batch; reuse the
            # one this prefill just built
            step_x = jnp.zeros((shape[0], 1) + tuple(shape[2:]),
                               dtype=jnp.dtype(dtype))
            t = min(shape[1], self.max_len - 1)
            dispatches += self._warm_widths(cache, step_x, t, widths,
                                            verifies, proposes)
        self.stats["warmed_dispatches"] += dispatches
        return dispatches

    def _warm_widths(self, cache, step_x, t, widths, verifies=(),
                     proposes=()) -> int:
        """Replay the decode convoy widths (and the verify (width, K)
        buckets) against one live cache — the shared tail of both warm
        paths. Falls back to a single-step decode when the peer never
        dispatched a fused convoy."""
        dispatches = 0
        for w in widths:
            outs = self.decode_many([cache] * w, [step_x] * w, [t] * w)
            jax.block_until_ready(outs[0][0])
            dispatches += 1
        if not widths:
            out, _ = self.decode(cache, step_x, t)
            jax.block_until_ready(out)
            dispatches += 1
        for w, k in verifies:
            vt = min(t, self.max_len - k)
            if vt < 0:
                continue
            vx = jnp.concatenate([step_x] * k, axis=1)
            outs = self.verify_many([cache] * w, [vx] * w, [vt] * w)
            jax.block_until_ready(outs[0][0])
            dispatches += 1
        for entry in proposes:
            _, p, kk = entry     # (batch, pending, k) — replayed at the
            pt = min(t, self.max_len - p - kk + 1)   # cache's own batch
            if pt < 0:
                continue
            px = jnp.concatenate([step_x] * p, axis=1)
            props, _ = self.propose_rollout(cache, px, pt, kk)
            jax.block_until_ready(props)
            dispatches += 1
        return dispatches

    def _warm_decode_only(self, profile: dict) -> int:
        """Decode-pool warm: the cache shape depends only on the session
        batch (caches are allocated at ``max_len`` regardless of prompt
        length), so one zero-filled donor cache per distinct batch shape
        covers every decode executable the peer has served."""
        dispatches = 0
        widths = list(profile.get("widths", []))
        verifies = list(profile.get("verify", []))
        batches = sorted({(shape[0], tuple(shape[2:]), dtype)
                          for shape, dtype in profile.get("prefill", [])})
        for bsz, tail, dtype in batches:
            cache = stage_init_cache(self.cfg, self.spec, bsz, self.max_len)
            step_x = jnp.zeros((bsz, 1) + tail, dtype=jnp.dtype(dtype))
            t = self.max_len - 1
            dispatches += self._warm_widths(cache, step_x, t, widths,
                                            verifies)
        self.stats["warmed_dispatches"] += dispatches
        return dispatches
