"""InferenceEngineV2: continuous batching over a paged KV cache.

Equivalent of the reference FastGen engine (``inference/v2/engine_v2.py:30``):
``put(uids, tokens)`` schedules a ragged batch -- new sequences prefill,
live sequences decode -- against a blocked KV cache, returning next-token
logits per sequence.  TPU-native mechanics:

* The KV pool is functional state ([num_blocks, block_size, N, D] per layer,
  sharded over tp on the head axis; int8 payload + fp32 scale pools when
  ``kv_cache.dtype == "int8"``); block *tables* are the only thing the
  host computes (``DSStateManager`` + ``BlockedAllocator``), matching the
  reference's host-side scheduler + device-side ragged kernels split.
* ONE compiled dispatch per scheduling round (the reference's
  one-forward-per-round contract, ``ragged_wrapper.py:31``): decodes are
  length-1 rows of the SAME bucketed ``[n_pad, s_pad]`` ragged batch as the
  prefills/extends, so a mixed round costs a single device round-trip
  instead of the former extend+decode pair -- and the jit cache is keyed
  only on the power-of-two (sequence count, max length) bucket, never the
  actual composition.  A pure-decode round buckets to ``s_pad == 1`` and
  takes the Pallas paged-decode kernel inside the model.
* Copy-on-write prefix sharing: the state manager queues (src, dst) block
  copies when a write would touch a shared block; the step applies them to
  every pool leaf BEFORE the KV scatter, as a fused gather-scatter (reads
  all sources from the pre-copy pool, so same-round reuse of a freed source
  block is safe).
* ``warmup(buckets)`` precompiles the pow-2 buckets at startup with a
  zero-length dummy round (every write masked off, KV pools pass through
  donated-but-unchanged), so first-token latency never pays a compile;
  ``infer/jit_cache_miss`` counts the compiles that do happen.
"""

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import comm as dist
from ...parallel import topology as topo
from ...telemetry import get_registry
from ...telemetry import serving as serving_events
from ...telemetry.registry import LATENCY_BUCKETS_S
from ...telemetry.trace import span
from ...utils.logging import log_dist
from ...ops.sampling import sample_tokens, verify_draft
from .config import RaggedInferenceEngineConfig
from .ragged_manager import DSStateManager

# rows this short still walk only their live KV blocks (the multi-token
# paged kernel); longer chunks take the dense gathered-blocks prefill path.
# Keep in sync with the S-routing in models/gpt_neox.py + models/llama.py.
SPEC_DECODE_WINDOW = 8


def _pow2_bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class RoundOutputs:
    """Everything a scheduling round produced, sampled ON DEVICE.

    ``tokens[row]`` holds the model's chosen token at each of the R scored
    trailing positions; with dk drafts right-aligned at offset
    ``offs = R - 1 - dk``, the row's NEW tokens are
    ``tokens[row, offs : offs + accepted + 1]`` (accepted drafts, which
    equal the model's choices by construction, plus one fresh token) --
    ``emitted(row)`` does that slice.  ``finite`` is the in-graph
    NaN/Inf check (the scheduler's circuit breaker reads it instead of
    scanning logits on the host).  ``logits`` is the LAST position's
    logits lane, a device array kept lazy: the decode hot path never
    forces it, only the compat ``put()`` wrapper and tests do.
    """

    uids: List
    tokens: np.ndarray       # [n, R] int32
    accepted: np.ndarray     # [n] int32, accepted-draft count per row
    draft_lens: np.ndarray   # [n] int32
    finite: np.ndarray       # [n] bool
    R: int
    logits: object = None    # device [n_pad, vocab] f32 (lazy)

    def emitted(self, row: int) -> np.ndarray:
        dk = int(self.draft_lens[row])
        a = min(int(self.accepted[row]), dk)
        offs = self.R - 1 - dk
        return self.tokens[row, offs:offs + a + 1]


def _round_seam(batch_uids, outputs):
    """Fault-injection seam on the scheduling round (the serving analog of
    the checkpoint engine's ``_io_open``/``_io_fsync``/``_io_replace``):
    ``tools/chaos.py`` patches this module attribute to simulate a slow
    step, non-finite logits, forced draft rejection (``spec_reject_storm``),
    or an OOM inside a round.  Receives and returns :class:`RoundOutputs`;
    production path is an identity passthrough."""
    return outputs


class InferenceEngineV2:
    def __init__(self, model, config=None, params=None, mesh=None, seed=0):
        import dataclasses

        if config is None:
            config = RaggedInferenceEngineConfig()
        elif isinstance(config, dict):
            config = RaggedInferenceEngineConfig(**config)
        self.config = config

        dist.init_distributed()
        if mesh is None:
            mesh = topo.MeshTopology(tp=config.tp_size)
        self.mesh = mesh
        topo.set_mesh(mesh)
        self._repl = NamedSharding(mesh.mesh, P())

        mcfg = dataclasses.replace(
            model.config, dtype=config.jnp_dtype,
            paged_num_blocks=config.kv_cache.num_blocks,
            paged_block_size=config.kv_cache.block_size,
            paged_kv_dtype=config.kv_cache.dtype)
        self.module = model.clone(config=mcfg, paged=True)

        self.state_manager = DSStateManager(config)
        self._max_blocks = self.state_manager.max_blocks_per_seq

        self._rng = jax.random.PRNGKey(seed)
        if params is None:
            params = self._init_params()
        else:
            from ..params import shard_module_params

            params = shard_module_params(self.module, self.mesh, params)
        self.params = params
        self.kv_cache = self._init_cache()
        self._step_fns = {}
        self._import_fn = None
        # host-RAM KV tier: spilled cache-only prefix blocks survive LRU
        # eviction in pinned host buffers and restore through the block
        # import path on the next match_prefix that wants them
        self.host_tier = None
        if config.kv_tier.enabled:
            from .kv_tier import HostKVTier

            self.host_tier = HostKVTier(config.kv_tier,
                                        read_block=self.export_kv_block,
                                        write_block=self.import_kv_block)
            self.state_manager.attach_host_tier(self.host_tier)
        # observability: one-dispatch-per-round is an acceptance criterion,
        # so the engine counts what actually hit the device
        self.dispatch_count = 0
        self.jit_cache_misses = 0
        self._round_stats = {"rounds": 0, "fed_tokens": 0, "padded_tokens": 0,
                             "decode_rows": 0}
        self._rounds_by_bucket = {}
        self.redundant_flush_count = 0
        self._kv_bytes_recorded = False

        n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
        log_dist(
            f"InferenceEngineV2: {n/1e6:.1f}M params | blocks="
            f"{config.kv_cache.num_blocks}x{config.kv_cache.block_size}"
            f"{' ' + config.kv_cache.dtype if config.kv_cache.quantized else ''} | "
            f"tp={mesh.tp}", ranks=[0])

    # ------------------------------------------------------------------ setup
    def _init_params(self):
        from ..params import init_module_params

        return init_module_params(self.module, self.mesh, self._rng,
                                  jnp.ones((1, 8), jnp.int32))

    def _init_cache(self):
        dummy = jnp.ones((1, 8), jnp.int32)
        shapes = jax.eval_shape(
            lambda: self.module.init(jax.random.PRNGKey(0), dummy))["cache"]
        # shard KV pools over tp on the heads axis (4-d int8/fp payload
        # pools AND 3-d fp32 scale pools -- heads is the last axis there)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(
                self.mesh.mesh,
                P(None, None, "tp", None) if len(s.shape) == 4
                else P(None, None, "tp")),
            shapes)
        return jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes),
            out_shardings=shardings)()

    # ----------------------------------------------------- block export/import
    # One physical block's KV, as the ordered leaf list of the cache pytree
    # (per layer: the [block_size, N, D] payload slice, plus the
    # [block_size, N] fp32 scale slice when the pool is int8).  The slice IS
    # the wire/spill format: int8 values + per-(slot, head) scales travel
    # as-is, so a prefill->decode migration or a host-tier spill/restore is
    # a memcpy, never a requantize.

    def export_kv_block_slices(self, block: int) -> List:
        """Lazy device slices of ``block`` from every KV pool leaf, in
        ``tree_leaves`` order.  Each slice is a NEW device array whose value
        is fixed at call time (the functional pool is immutable), so the
        caller may ``device_put`` them asynchronously while later rounds
        replace ``self.kv_cache``."""
        return [leaf[block] for leaf in
                jax.tree_util.tree_leaves(self.kv_cache)]

    def export_kv_block(self, block: int) -> List[np.ndarray]:
        """Host copies of ``block``'s KV (the spill format): numpy arrays
        in ``tree_leaves`` order."""
        return [np.asarray(x)
                for x in jax.device_get(self.export_kv_block_slices(block))]

    def import_kv_block(self, block: int, payloads: List) -> None:
        """Write ``payloads`` (host or device arrays, ``tree_leaves``
        order, as produced by ``export_kv_block*``) into physical block
        ``block`` of every pool leaf -- one jitted donating dispatch, the
        restore/adoption half of migration and the host tier."""
        leaves, treedef = jax.tree_util.tree_flatten(self.kv_cache)
        if len(payloads) != len(leaves):
            raise ValueError(
                f"block payload has {len(payloads)} leaves, pool has "
                f"{len(leaves)}")
        if self._import_fn is None:
            def _imp(cache, idx, blk):
                return jax.tree_util.tree_map(
                    lambda leaf, p: leaf.at[idx].set(p.astype(leaf.dtype)),
                    cache, blk)

            self._import_fn = jax.jit(_imp, donate_argnums=(0,))
        blk = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(p) for p in payloads])
        self.kv_cache = self._import_fn(self.kv_cache, jnp.int32(block), blk)

    @property
    def kv_block_bytes(self) -> int:
        """Bytes one physical block occupies across all pool leaves -- the
        unit of migration/spill accounting."""
        return self.kv_pool_bytes // self.config.kv_cache.num_blocks

    def longctx_session(self, **kwargs):
        """Open a :class:`~.longctx.LongContextSession` on this engine:
        single-sequence serving where cold middle KV blocks live in the
        host tier and stream back under issue-ahead prefetch, so context
        grows past the pool while HBM stays at the hot working set."""
        from .longctx import LongContextSession

        return LongContextSession(self, **kwargs)

    # --------------------------------------------------------------- compiled
    def _build_step(self, n_pad, s_pad, r_pad):
        """ONE compiled forward for an entire scheduling round -- prefills,
        SplitFuse extends, decodes (length-1 rows), and speculative decodes
        (length-(k+1) rows: last committed token + k drafts) together in a
        single ``[n_pad, s_pad]`` ragged batch (reference
        one-forward-per-round, ``ragged_wrapper.py:31``).  The jit cache is
        keyed on the (sequence-count, length, verify-width) power-of-two
        bucket, never on the batch's actual composition.

        Everything after the forward ALSO runs in-graph: the head projects
        each row's ``r_pad`` trailing positions, token selection
        (greedy/temperature/top-k/top-p per ``SamplingConfig``) picks one
        token per position, and ``verify_draft`` computes the
        longest-accepted-prefix over the drafts -- so a round returns
        ``(chosen tokens, accepted counts, finite flags)`` with zero host
        sampling round-trips.  The last position's logits ride along as a
        lazy lane for the compat ``put()`` API and the NaN chaos seam."""
        model = self.module
        sc = self.config.sampling

        def step(params, cache, tokens, starts, lengths, tables,
                 copy_src, copy_dst, draft_tokens, draft_lens, nonce):
            # copy-on-write block copies FIRST: a single vectorized
            # gather-scatter per pool leaf.  Sources are gathered from the
            # pre-copy pool (read-before-write even if a source was
            # reallocated as another row's destination this round); padded
            # rows use dst == num_blocks, dropped by the OOB scatter.
            with jax.named_scope("kv_scatter"):
                cache = jax.tree_util.tree_map(
                    lambda pool: pool.at[copy_dst].set(pool[copy_src],
                                                       mode="drop"),
                    cache)
            positions = starts[:, None] + jnp.arange(s_pad)[None]   # [n, S]
            write_mask = jnp.arange(s_pad)[None] < lengths[:, None]  # [n, S]
            # ragged logits-gather: the head projects ONLY each row's
            # r_pad trailing real tokens (clamped to 0 on short/padded
            # rows; surplus columns fall in the ignored left pad of the
            # right-aligned draft layout) -- no [n, s_pad, vocab] buffer
            last = jnp.maximum(lengths - 1, 0)
            gather = jnp.maximum(
                last[:, None] - (r_pad - 1) + jnp.arange(r_pad)[None], 0)
            logits, mut = model.apply(
                {"params": params, "cache": cache}, tokens,
                deterministic=True, positions=positions,
                paged_state={"block_tables": tables, "write_mask": write_mask},
                logits_positions=gather,
                mutable=["cache"])
            logits = logits.astype(jnp.float32)           # [n, R, V]
            finite = jnp.isfinite(logits).all(axis=(1, 2))
            # per-round PRNG key derived in-graph from the traced nonce:
            # advancing the stream never recompiles, and greedy config
            # (temperature <= 0) compiles the key away entirely
            with jax.named_scope("sample"):
                key = jax.random.fold_in(jax.random.PRNGKey(sc.seed), nonce)
                chosen = sample_tokens(logits, key,
                                       temperature=sc.temperature,
                                       top_k=sc.top_k, top_p=sc.top_p)
                accepted = verify_draft(chosen, draft_tokens, draft_lens)
            return chosen, accepted, finite, logits[:, -1], mut["cache"]

        return jax.jit(step, donate_argnums=(1,))

    def _get_step_fn(self, n_pad, s_pad, r_pad):
        key = (n_pad, s_pad, r_pad)
        if key not in self._step_fns:
            self._step_fns[key] = self._build_step(n_pad, s_pad, r_pad)
            self.jit_cache_misses += 1
            reg = get_registry()
            if reg.enabled:
                reg.counter("infer/jit_cache_miss").inc(
                    n_pad=n_pad, s_pad=s_pad, r_pad=r_pad)
        return self._step_fns[key]

    def _round_buckets(self, n_seqs: int, max_len: int,
                       max_draft: int = 0) -> Tuple[int, int, int]:
        """A pure-decode round buckets to s_pad == 1 (the model's Pallas
        paged-decode path); speculative-decode rounds bucket to small pow-2
        lengths <= SPEC_DECODE_WINDOW (the multi-token paged path);
        mixed/prefill rounds pad length to pow2 >= 16 to bound the bucket
        count.  r_pad is the verify width: pow2(max drafts + 1)."""
        n_pad = _pow2_bucket(n_seqs, lo=1)
        if max_len == 1:
            s_pad = 1
        elif max_len <= SPEC_DECODE_WINDOW:
            s_pad = _pow2_bucket(max_len, lo=2)
        else:
            s_pad = _pow2_bucket(max_len)
        r_pad = _pow2_bucket(max_draft + 1, lo=1)
        return n_pad, s_pad, r_pad

    def warmup(self, buckets: Optional[Sequence[Tuple]] = None):
        """Precompile the compiled-step buckets before serving traffic
        (first-token latency otherwise pays a full XLA compile per new
        bucket).  ``buckets`` entries are (sequence-count, max-chunk-length)
        or (sequence-count, max-chunk-length, max-drafts) tuples, rounded up
        to their pow-2 bucket; default: the pure-decode round at full decode
        width, a full-budget prefill round, and -- when speculation is
        enabled -- the (k+1)-row speculative-decode bucket, so steady-state
        speculation adds ZERO jit cache misses.

        The warmup round is a zero-length dummy: every row has length 0, so
        all KV writes mask off and the donated pools come back bit-identical
        -- compiling through the REAL jit path (an AOT ``.lower().compile()``
        would not populate the jit call cache the serving path hits).
        """
        smc = self.config.state_manager
        spec = self.config.speculative
        if buckets is None:
            buckets = [
                (smc.max_decode_batch, 1, 0),
                (min(smc.max_ragged_sequence_count, smc.max_decode_batch),
                 smc.max_ragged_batch_size, 0),
            ]
            if spec.enabled:
                # one bucket per distinct draft width: an n-gram drafter
                # returns ANY length in [0, k] depending on its match, and
                # a mid-serve compile would read as a latency spike
                for dk in range(1, spec.k + 1):
                    buckets.append((smc.max_decode_batch, dk + 1, dk))
        compiled = []
        for b in buckets:
            n, s, dk = b if len(b) == 3 else (b[0], b[1], 0)
            n_pad, s_pad, r_pad = self._round_buckets(int(n), int(s), int(dk))
            if (n_pad, s_pad, r_pad) in compiled:
                continue
            compiled.append((n_pad, s_pad, r_pad))
            fn = self._get_step_fn(n_pad, s_pad, r_pad)
            zeros_i = np.zeros((n_pad,), np.int32)
            out = fn(
                self.params, self.kv_cache,
                jnp.zeros((n_pad, s_pad), jnp.int32),
                jnp.asarray(zeros_i), jnp.asarray(zeros_i),
                jnp.zeros((n_pad, self._max_blocks), jnp.int32),
                jnp.asarray(zeros_i),
                jnp.full((n_pad,), self.config.kv_cache.num_blocks, jnp.int32),
                jnp.zeros((n_pad, r_pad - 1), jnp.int32),
                jnp.asarray(zeros_i), jnp.int32(0))
            self.kv_cache = out[-1]
        jax.block_until_ready(self.kv_cache)
        return compiled

    # ------------------------------------------------------------- public API
    def put_round(self, batch_uids: List, batch_tokens: List,
                  batch_drafts: Optional[List] = None) -> RoundOutputs:
        """Schedule a ragged batch -- ONE compiled dispatch for the whole
        round, with sampling and draft verification in-graph.

        ``batch_tokens[i]`` are the tokens to feed for uid i (a prompt
        chunk, or the single last-accepted token of a decode);
        ``batch_drafts[i]`` (optional) appends up to k speculated
        continuation tokens to that row.  The step verifies the drafts
        against the model's own choices (longest accepted prefix), the
        engine commits exactly the fed tokens whose KV is valid
        (``fed - dk + accepted``) and releases the never-committed draft
        tail blocks (refcount -> 0, the COW-fork rollback -- no KV rewind).
        Returns :class:`RoundOutputs`; row i corresponds to input i.
        """
        assert len(batch_uids) == len(batch_tokens)
        with span("serve/round", dispatch=self.dispatch_count) as round_span:
            return self._put_round(round_span, batch_uids, batch_tokens,
                                   batch_drafts)

    def round_stats(self) -> dict:
        """What ``put_round`` has fed and what it padded that to, counted
        on every round: rounds, fed tokens, padded tokens (``n_pad *
        s_pad``), decode rows, rounds by bucket key ``(n_pad, s_pad,
        r_pad)`` and step programs built."""
        return {**self._round_stats,
                "rounds_by_bucket": dict(self._rounds_by_bucket),
                "step_programs_built": self.jit_cache_misses}

    def _put_round(self, round_span, batch_uids, batch_tokens, batch_drafts):
        t_start = time.perf_counter()
        sm = self.state_manager
        smc = self.config.state_manager
        if batch_drafts is None:
            batch_drafts = [None] * len(batch_uids)
        assert len(batch_drafts) == len(batch_uids)

        with span("serve/round/plan"):
            ops, n_decodes, total_tokens, max_len, max_dk = [], 0, 0, 1, 0
            for i, (uid, toks, draft) in enumerate(
                    zip(batch_uids, batch_tokens, batch_drafts)):
                toks = np.asarray(toks, np.int32).reshape(-1)
                if toks.size == 0:
                    raise ValueError(f"empty token list for uid {uid}")
                draft = (np.asarray(draft, np.int32).reshape(-1)
                         if draft is not None else np.zeros((0,), np.int32))
                dk = int(draft.size)
                if dk:
                    # drafts ride as ordinary fed tokens of the same row:
                    # their KV scatters like any token's, verification is
                    # just the logits of the positions they occupy
                    toks = np.concatenate([toks, draft])
                total_tokens += toks.size
                max_len = max(max_len, toks.size)
                max_dk = max(max_dk, dk)
                # decode = the sequence has KV *landed* (seen_tokens > 0),
                # not merely reserved: the SplitFuse scheduler pre-reserves
                # blocks via sm.extend before the prompt runs, so a known uid
                # with a 1-token chunk can still be a prefill tail.
                # Classification is observability-only now -- decodes run as
                # length-1 rows of the same fused step, so there is no
                # separate width to overflow.
                if sm.known(uid) and toks.size - dk == 1 \
                        and sm.get_sequence(uid).seen_tokens > 0:
                    n_decodes += 1
                ops.append((i, uid, toks, dk))

            # validate the whole batch BEFORE mutating any sequence state, so
            # a rejected put can be retried without corrupting
            # seen_tokens/blocks
            if len(batch_uids) > smc.max_ragged_sequence_count:
                raise ValueError(
                    f"{len(batch_uids)} sequences exceed "
                    f"max_ragged_sequence_count="
                    f"{smc.max_ragged_sequence_count}")
            if total_tokens > smc.max_ragged_batch_size:
                raise ValueError(
                    f"{total_tokens} tokens exceed max_ragged_batch_size="
                    f"{smc.max_ragged_batch_size}")
            # KV capacity + tracked-sequence dry-run BEFORE any mutation
            # (also rejects duplicate uids -- one DSSequenceDescriptor slot
            # per uid per ragged batch), so a MemoryError cannot fire
            # mid-batch after earlier sequences already committed
            # seen_tokens/blocks
            sm.validate_batch([(uid, toks.size) for _, uid, toks, _ in ops])

            n_pad, s_pad, r_pad = self._round_buckets(len(ops), max_len,
                                                      max_dk)
            fn = self._get_step_fn(n_pad, s_pad, r_pad)
            tokens = np.zeros((n_pad, s_pad), np.int32)
            starts = np.zeros((n_pad,), np.int32)
            lengths = np.zeros((n_pad,), np.int32)
            tables = np.zeros((n_pad, self._max_blocks), np.int32)
            draft_tokens = np.zeros((n_pad, r_pad - 1), np.int32)
            draft_lens = np.zeros((n_pad,), np.int32)
            for row, (i, uid, toks, dk) in enumerate(ops):
                seq = sm.extend(uid, toks.size)
                tokens[row, :toks.size] = toks
                starts[row] = seq.seen_tokens
                lengths[row] = toks.size
                tables[row] = sm.block_table(uid, pad_to=self._max_blocks)
                if dk:
                    # right-aligned so the verifier's cumulative-prefix
                    # trick works on ragged draft counts (left pad = vacuous
                    # match)
                    draft_tokens[row, r_pad - 1 - dk:r_pad - 1] = toks[-dk:]
                    draft_lens[row] = dk
            # copy-on-write block copies queued by the extends (incl. the
            # scheduler's pre-reserving extends for this round): at most one
            # per row, padded with an OOB destination that the scatter drops
            copies = sm.take_pending_copies()
            if len(copies) > n_pad:
                raise RuntimeError(
                    f"{len(copies)} pending COW copies exceed the round's "
                    f"{n_pad} rows")
            copy_src = np.zeros((n_pad,), np.int32)
            copy_dst = np.full((n_pad,), self.config.kv_cache.num_blocks,
                               np.int32)
            for c, (src, dst) in enumerate(copies):
                copy_src[c], copy_dst[c] = src, dst

        stats = self._round_stats
        stats["rounds"] += 1
        stats["fed_tokens"] += int(total_tokens)
        stats["padded_tokens"] += n_pad * s_pad
        stats["decode_rows"] += n_decodes
        bucket = (n_pad, s_pad, r_pad)
        self._rounds_by_bucket[bucket] = \
            self._rounds_by_bucket.get(bucket, 0) + 1
        round_span.set(n_seqs=len(ops), n_tokens=int(total_tokens),
                       decodes=n_decodes, n_pad=n_pad, s_pad=s_pad,
                       r_pad=r_pad)

        with span("serve/round/upload"):
            device_args = [jnp.asarray(a) for a in (
                tokens, starts, lengths, tables, copy_src, copy_dst,
                draft_tokens, draft_lens)]
        with span("serve/round/dispatch"):
            chosen, accepted, finite, last_logits, self.kv_cache = fn(
                self.params, self.kv_cache, *device_args,
                jnp.int32(self.dispatch_count))
        self.dispatch_count += 1
        with span("serve/round/harvest"):    # the round's one sync
            outputs = RoundOutputs(
                uids=list(batch_uids),
                tokens=np.asarray(chosen)[:len(ops)],
                accepted=np.asarray(accepted)[:len(ops)],
                draft_lens=draft_lens[:len(ops)].copy(),
                finite=np.asarray(finite)[:len(ops)],
                R=r_pad,
                logits=last_logits)
        # chaos seam (identity in production): may delay, corrupt, or raise
        # -- BEFORE commit_tokens, so an injected round failure leaves
        # sequence bookkeeping exactly as a real device fault would
        outputs = _round_seam(batch_uids, outputs)

        drafted_total, accepted_total, emitted_total = 0, 0, 0
        with span("serve/round/commit"):
            for row, (i, uid, toks, dk) in enumerate(ops):
                a = min(int(outputs.accepted[row]), dk)
                # fed tokens whose KV is VALID: everything up to the last
                # accepted draft (accepted drafts equal the model's choices,
                # so their KV is exactly what non-speculative decoding would
                # have written); rejected drafts' fed tokens are not committed
                sm.commit_tokens(uid, toks[:toks.size - dk + a])
                if dk:
                    # rejection = drop the forked tail: blocks wholly beyond
                    # the committed range free at refcount 0 (accepted tails
                    # keep theirs -- this is a no-op then)
                    sm.rollback_draft_tail(uid)
                    drafted_total += dk
                    accepted_total += a
                emitted_total += a + 1

        reg = get_registry()
        if reg.enabled:
            # np.asarray above already synced the dispatch, so the wall
            # time covers the full ragged round
            dt = time.perf_counter() - t_start
            reg.counter("inference/tokens_total").inc(total_tokens)
            reg.scalar("inference/tokens_per_sec").record(
                total_tokens / max(dt, 1e-9))
            reg.histogram("inference/put_latency_s",
                          buckets=LATENCY_BUCKETS_S).observe(
                dt, extends=len(ops) - n_decodes, decodes=n_decodes)
            reg.counter("infer/dispatches").inc()
            serving_events.emit_speculation(drafted_total, accepted_total,
                                            emitted_total, len(ops))
            alloc = sm.allocator
            reg.scalar("infer/cache_util").record(
                alloc.allocated_blocks / alloc.total_blocks)
            if not self._kv_bytes_recorded:
                self._kv_bytes_recorded = True
                reg.scalar("infer/kv_bytes").record(
                    float(self.kv_pool_bytes),
                    dtype=self.config.kv_cache.dtype or self.config.dtype)
        return outputs

    def put(self, batch_uids: List, batch_tokens: List) -> np.ndarray:
        """Schedule a ragged batch; returns next-token logits [n, vocab]
        in input order (reference ``engine_v2.put``).  Compat wrapper over
        :meth:`put_round` -- forcing the logits lane to the host is exactly
        the round-trip the token-level API avoids, so new callers should
        consume ``put_round(...).emitted(row)`` instead."""
        out = self.put_round(batch_uids, batch_tokens)
        return np.asarray(out.logits)[:len(batch_uids)]

    @property
    def kv_pool_bytes(self) -> int:
        """Total HBM bytes of the KV pools (payload + scales, all layers) --
        the denominator of the int8 capacity win."""
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(self.kv_cache))

    def flush(self, uid) -> bool:
        """Free a finished sequence (reference ``flush``).  Idempotent: the
        cancellation paths above (deadline sweeps, breaker teardown, double
        finish) reach here with unknown/already-flushed uids routinely --
        that is a counted no-op, never a KeyError.  Returns whether a
        tracked sequence was actually released."""
        if not self.state_manager.known(uid):
            self.redundant_flush_count += 1
            reg = get_registry()
            if reg.enabled:
                reg.counter("infer/redundant_flush").inc(uid=str(uid))
            return False
        self.state_manager.flush_sequence(uid)
        return True

    @property
    def free_blocks(self) -> int:
        return self.state_manager.allocator.free_blocks

    # ------------------------------------------------------------ convenience
    def generate(self, prompts: List[np.ndarray], max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 drafter=None) -> List[np.ndarray]:
        """Continuous-batching loop over ``put_round`` (serving-loop demo;
        the reference leaves sampling to the MII layer above).  Token
        selection happens on-device per ``SamplingConfig`` (greedy by
        default); pass a ``drafter`` (e.g. ``speculative.NGramDrafter``)
        to run self-speculative decoding -- each accepted draft is one
        fewer scheduling round."""
        spec_k = self.config.speculative.k if drafter is not None else 0
        uids = list(range(len(prompts)))
        outs = [list(int(t) for t in np.asarray(p).reshape(-1))
                for p in prompts]
        live = set(uids)
        out = self.put_round(uids, prompts)
        nxt = {}
        for i, u in enumerate(uids):
            tok = int(out.tokens[i, -1])
            outs[u].append(tok)
            nxt[u] = tok
            if eos_token_id is not None and tok == eos_token_id:
                live.discard(u)
        done = {u: len(outs[u]) - len(np.asarray(prompts[u]).reshape(-1))
                for u in uids}
        while live and any(done[u] < max_new_tokens for u in live):
            batch = sorted(live)
            drafts = [drafter.propose(outs[u], spec_k) if drafter else None
                      for u in batch]
            out = self.put_round(batch, [[nxt[u]] for u in batch], drafts)
            for i, u in enumerate(batch):
                for tok in (int(t) for t in out.emitted(i)):
                    outs[u].append(tok)
                    nxt[u] = tok
                    done[u] += 1
                    if (eos_token_id is not None and tok == eos_token_id) \
                            or done[u] >= max_new_tokens:
                        live.discard(u)
                        break
        for u in uids:
            self.flush(u)
        return [np.asarray(o, np.int32) for o in outs]
