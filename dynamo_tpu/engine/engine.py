"""The JAX engine: jit-compiled prefill/decode over a paged KV cache with
continuous batching.

This is the TPU-native replacement for the reference's consumed engine workers
(`python3 -m dynamo.vllm` / `dynamo.sglang` / `dynamo.trtllm`,
/root/reference/examples/deploy/vllm/agg.yaml:29-35). Key properties:

- **Shape-static decode**: every decode step runs the full `max_num_seqs`
  batch; inactive slots point at the reserved trash page. One compiled
  program, zero recompiles in steady state.
- **Bucketed prefill**: prompt lengths are padded to power-of-two buckets
  (multiples of page_size), so at most log2(max_seq_len/page_size)+1 prefill
  programs are ever compiled. This is the recompile-control strategy that
  replaces the TRT engine-build step (SURVEY.md §7 hard part #3).
- **Sampling fused in-jit** with the decode step: one device round-trip per
  step, returning only the [B] int32 next-token array to the host.
- **Donated KV buffers**: the page pools are donated to each jit call, so XLA
  updates them in place in HBM.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import inspect
import logging
import math
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.kv_cache import (
    KVCacheSpec,
    OutOfPages,
    PageAllocator,
    PrefixCache,
    SeqState,
    WindowRings,
    alloc_kv_pages,
    alloc_state_snapshots,
    restore_state,
    save_state,
)
from dynamo_tpu.engine.request import GenRequest, TokenEvent
from dynamo_tpu.engine import sampling as smp
from dynamo_tpu.lora.registry import NoFreeAdapterSlot
from dynamo_tpu.models import llama
from dynamo_tpu.ops import attention as att_ops
from dynamo_tpu.ops import json_guide
from dynamo_tpu.ops import ssm as ssm_ops
from dynamo_tpu.ops.moe import MOE_STATS
from dynamo_tpu.ops import sparse_blocks
from dynamo_tpu.ops.sparse_blocks import CHUNK_STATS
from dynamo_tpu.models.config import SLIDING, ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh
from dynamo_tpu.parallel import sharding as shd
from dynamo_tpu.robustness import faults
from dynamo_tpu.robustness.watchdog import (
    EngineWatchdog,
    IntegrityFault,
    integrity_mode,
)

log = logging.getLogger("dynamo_tpu.engine")


def _pack_logit_bias(req: GenRequest):
    """Pack a request's {token_id: bias} map into fixed [BIAS_K] lanes
    (-1 = empty) so the jitted sampler stays shape-static. Oversized maps
    raise — the HTTP layer already rejects them; direct library callers
    must not have bans silently dropped."""
    ids = np.full((smp.BIAS_K,), -1, np.int32)
    vals = np.zeros((smp.BIAS_K,), np.float32)
    if req.logit_bias:
        if len(req.logit_bias) > smp.BIAS_K:
            raise ValueError(
                f"logit_bias has {len(req.logit_bias)} entries; the engine "
                f"supports at most {smp.BIAS_K}")
        for i, (tok, b) in enumerate(req.logit_bias.items()):
            ids[i] = int(tok)
            vals[i] = float(b)
    return ids, vals


def _wait_phase(seq: SeqState) -> Optional[Dict[str, float]]:
    """What the event that ends `seq` carries on TokenEvent.phase: its
    token time by cause (timeline.TokenWait.phase); None without one."""
    wait = seq.token_wait
    return None if wait is None else wait.phase()


def _argnums(fn, *names: str) -> Tuple[int, ...]:
    """Positions of the parameters called `names` in `fn`'s signature (a
    `*varargs` name gives the position of its first operand). Raises
    ValueError for a name the signature does not have."""
    params = list(inspect.signature(fn).parameters)
    return tuple(params.index(n) for n in names)


def _next_bucket(n: int, page_size: int, max_len: int) -> int:
    """Smallest power-of-two multiple of page_size >= n (capped at max_len
    rounded up to a page multiple, so the bucket always page-aligns)."""
    cap = -(-max_len // page_size) * page_size
    b = page_size
    while b < n:
        b *= 2
    return min(b, cap)


class PhaseTimer:
    """Bucketed per-phase latency histogram (quarter-octave log buckets,
    0.25ms..8s — worst-case quantile error ~9% vs the octave buckets' 2x).

    The in-engine observability VERDICT/SURVEY §5 call for: per-phase
    step-time distributions (not just cumulative sums), cheap enough to run
    always-on in the hot loop."""

    _EDGES_MS = [0.25 * 2 ** (i / 4) for i in range(61)]  # 0.25ms .. ~8.2s

    def __init__(self):
        self.count = 0
        self.sum_s = 0.0
        self.max_s = 0.0
        self.buckets = [0] * (len(self._EDGES_MS) + 1)

    def observe(self, seconds: float, weight: int = 1) -> None:
        """Record `weight` observations of `seconds` (a fused window's
        per-step time counts once PER STEP, so a tail of 1-step windows
        cannot outvote the steady-state windows in the quantiles)."""
        self.count += weight
        self.sum_s += seconds * weight
        if seconds > self.max_s:
            self.max_s = seconds
        # first edge >= ms (the last bucket takes what passes every edge)
        self.buckets[bisect.bisect_left(self._EDGES_MS,
                                        seconds * 1e3)] += weight

    def quantile_ms(self, q: float) -> float:
        """Geometric-midpoint estimate of the q-quantile from the buckets."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        seen = 0
        for i, n in enumerate(self.buckets):
            seen += n
            if seen >= target:
                if i >= len(self._EDGES_MS):
                    # overflow bucket: the top edge is a LOWER bound here
                    return self._EDGES_MS[-1]
                hi = self._EDGES_MS[i]
                lo_edge = self._EDGES_MS[i - 1] if i > 0 else hi / 2 ** 0.25
                return (lo_edge * hi) ** 0.5
        return self._EDGES_MS[-1]

    def snapshot(self) -> Dict[str, float]:
        return {
            "count": self.count,
            "sum_s": round(self.sum_s, 6),
            "mean_ms": round(1e3 * self.sum_s / self.count, 3)
            if self.count else 0.0,
            "p50_ms": round(self.quantile_ms(0.5), 3),
            "p95_ms": round(self.quantile_ms(0.95), 3),
            "max_ms": round(self.max_s * 1e3, 3),
        }


class EngineMetrics:
    """Counters + per-phase timing histograms surfaced via /worker/stats."""

    _PHASES = ("prefill", "prefill_chunk", "decode_window", "decode_step",
               "mixed_step")
    # decode-window batch occupancy (active slots / max_num_seqs) —
    # persistently low occupancy means max_num_seqs is oversized (padded
    # rows burn HBM stream for nothing); the exposition bridge
    # (observability/engine_metrics.py) serves it as a histogram
    _OCC_EDGES = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)
    # accepted-draft count per speculating slot per verify step (0 = the
    # window emitted only its non-speculative token). K is bounded by
    # page_size, so fixed small-integer edges cover every configuration;
    # the exposition bridge serves this as
    # dynamo_engine_spec_accepted_length (observability/engine_metrics.py)
    _SPEC_EDGES = (0, 1, 2, 3, 4, 6, 8)

    def __init__(self):
        self.num_requests = 0
        self.num_finished = 0
        self.prompt_tokens = 0
        self.output_tokens = 0
        self.decode_steps = 0
        self.prefill_time_s = 0.0
        self.decode_time_s = 0.0
        self.kv_oom = 0
        self.num_preempted = 0  # recompute preemptions under page pressure
        # speculative decoding: drafts offered vs accepted (acceptance rate
        # = accepted / drafted; bonus tokens not counted in either)
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_accept_buckets = [0] * (len(self._SPEC_EDGES) + 1)
        self.spec_accept_sum = 0
        self.spec_accept_count = 0
        # Speculation v3: the same spec series split per drafter (ngram |
        # model) — the exposition bridge serves these as the `drafter`
        # label on the spec counters/histogram so n-gram vs model
        # acceptance is separable on one scrape
        self.spec_draft_by: Dict[str, int] = {}
        self.spec_accepted_by: Dict[str, int] = {}
        self.spec_hist_by: Dict[str, List[int]] = {}
        self.spec_sum_by: Dict[str, int] = {}
        self.spec_count_by: Dict[str, int] = {}
        self.occupancy_buckets = [0] * (len(self._OCC_EDGES) + 1)
        self.occupancy_sum = 0.0
        self.occupancy_count = 0
        # unified ragged step composition: fraction of each mixed window's
        # rows that were prefill-chunk tokens (persistently high fractions
        # mean --mixed-batch-tokens crowds decode; near-zero means the
        # budget is slack and admission latency is chunk-bound)
        self.mixed_buckets = [0] * (len(self._OCC_EDGES) + 1)
        self.mixed_sum = 0.0
        self.mixed_count = 0
        # mixed steps dispatched while a program was still unread (behind a
        # window or the chunk before them, Engine._mixed_step), counted at
        # dispatch; over mixed_count: how often a prompt's chunk found the
        # device busy instead of draining it first
        self.mixed_behind = 0
        # finishes applied while a program was in flight and WITHOUT reading
        # it first (Engine._finish_slot: the slot was retired in the device
        # carry, counted ahead or found at the read), over num_finished;
        # and the high-water mark of the pages (a sequence's own and its
        # ring's) that waited for a program to be read before they went
        # back to their allocator (Engine._held)
        self.finishes_behind = 0
        self.held_pages_peak = 0
        # first tokens given (Engine._finalize_admission, every path), and
        # those of them that the final chunk's own program sampled and
        # installed in the device carry while it stayed in flight
        # (Engine._mixed_step: read one program late, nothing drained)
        self.num_admitted = 0
        self.first_tokens_behind = 0
        # decode windows dispatched (Engine._dispatch_window; a mixed step
        # or a verify is no window): the programs, the decode steps they
        # fused, and the programs of 2 .. num_scheduler_steps - 1 steps,
        # cut to the shortest headroom of their batch (_window_steps)
        self.windows: Dict[str, int] = {"programs": 0, "steps": 0,
                                        "short": 0}
        self.phases: Dict[str, PhaseTimer] = {p: PhaseTimer()
                                              for p in self._PHASES}
        # a first token by stage, cumulative seconds over `count` requests
        # (serving/api.py GenerationHandle observes, from handler threads):
        # received -> submitted -> prefill start -> first TokenEvent ->
        # first frame written; the four stages sum to ttft_s
        self.first_token: Dict[str, float] = {
            "count": 0, "submit_s": 0.0, "queue_s": 0.0, "prefill_s": 0.0,
            "emit_s": 0.0, "ttft_s": 0.0}
        self._first_token_lock = threading.Lock()
        # what the grouped expert layers counted (ops/moe.MOE_STATS), summed
        # over layers and steps. Each step program returns its counts as a
        # small device array; they wait in _moe_pending and are folded in
        # when read, so the step loop never waits on one
        # what the paged attention kernels of an MLA model were asked for
        # (all zero for any other model), counted on the host at dispatch,
        # a layer's worth (the benchmark's roofline
        # shares multiply by the layers): query rows, and KV rows read —
        # a decode row reads its whole context once; a chunk's query block
        # of 8 reads its causal horizon (chunk_block_kv_rows) and each of
        # its tokens scores its own (chunk_kv_pairs)
        self.attn: Dict[str, int] = {
            "decode_q_rows": 0, "decode_kv_rows": 0,
            "mixed_decode_q_rows": 0, "mixed_decode_kv_rows": 0,
            "mixed_chunk_q_rows": 0, "mixed_chunk_block_kv_rows": 0,
            "mixed_chunk_kv_pairs": 0}
        # the same counts BY KIND for a model whose layers are of more than
        # one kind (all zero for any other model; `attn` above stays zero
        # for it): a layer's worth a step of what the paged kernels were
        # asked for on a full layer and on a sliding one, where a query's
        # KV rows are those within reach: min(context, sliding_window)
        self.attn_kinds: Dict[str, Dict[str, int]] = {
            kind: dict.fromkeys(self.attn, 0) for kind in ("full", "window")}
        # query rows of a sliding layer whose softmax carried a learned
        # sink (a layer's worth a step, like the rest; zero for a model
        # whose sliding layers have none)
        self.attn_kinds["window"]["sink_rows"] = 0
        # prefix-cache hits a model of kinds turned into misses (a hit is
        # exact only if the sliding layers' rows before it are still held;
        # a ring is its sequence's own, so none is), and those of a hybrid
        # model: a hit at block b needs the state at b. A state larger than
        # a block's KV (Mamba-2's) is kept nowhere and every found prefix
        # counts; where states are kept (KVCacheSpec.state_kept_at_blocks)
        # only the hits that found pages and no snapshot at any of them
        self.prefix_hits_inexact = 0
        # what the Mamba-2 layers of a hybrid model were asked for (all zero
        # for any other model), counted like `attn`: on the host at
        # dispatch, a layer's worth. decode_rows: live rows x steps, each
        # one state read and written; chunk_tokens / chunk_calls: prompt
        # tokens the chunked scan ran over and the programs that ran it;
        # layer_steps: steps a Mamba-2 layer ran (a fused window counts its
        # steps); slots_touched: state slots the one-token update read and
        # wrote (the live rows under the kernel, every slot under its XLA
        # twin: ops/ssm.update; a program of prompt rows alone runs none)
        self.ssm: Dict[str, int] = {
            "decode_rows": 0, "chunk_tokens": 0, "chunk_calls": 0,
            "layer_steps": 0, "slots_touched": 0}
        # the same five for the gated short-convolution layers of an
        # operator-then-FFN model (lfm2_moe; zero for any other, and `ssm`
        # stays zero for it): decode_rows / slots_touched are rows of
        # [B | C | u] gated and tapped and slots whose K-1 rows a layer-step
        # read and wrote (every slot's: ops/short_conv.gated_step takes the
        # layer's slots as one block), chunk_tokens / chunk_calls the
        # prompt rows convolved from a slot's rows to a slot's rows
        self.conv: Dict[str, int] = dict.fromkeys(self.ssm, 0)
        # admission passes (Engine._admit) that left the queue's head
        # waiting, by the store of a hybrid model that lacked room for it:
        # no free state slot, too few free pages for its prompt, or both
        # (then both count). All zero for a model without state slots
        self.admit_blocked: Dict[str, int] = {"state_slots": 0, "pages": 0}
        # what the sparse-attention indexer of a DeepSeek-V3.2-style model
        # was asked for (all zero for any other model), counted like
        # `attn`: on the host at dispatch, a layer's worth. A query in a
        # program that runs the selection (models/llama._selects: its page
        # table addresses more than index_topk tokens) scores every token
        # of its context and attends over min(index_topk, context) selected
        # rows; one whose context is at most index_topk (every token is
        # selected, or the program keeps today's kernels and scores
        # nothing) also counts in queries_unselected
        self.dsa: Dict[str, int] = {
            "decode_queries": 0, "decode_keys_scored": 0,
            "chunk_queries": 0, "chunk_keys_scored": 0,
            "rows_selected": 0, "queries_unselected": 0}
        # what the block-sparse attention layers of a minicpm_sala model were
        # asked for (all zero for any other model), counted like `attn`: on
        # the host at dispatch, a layer's worth. decode_rows: live rows x
        # steps; dense_rows: those at or under sparse_dense_len (they attend
        # their whole context); a row past it scores keys_scored pooled
        # keys, attends blocks_selected blocks (blocks_forced of them the
        # initial ones and the local window) holding rows_attended rows of
        # the rows_in_context it has (a dense row: all of them);
        # chunk_calls: chunks whose last query stood past sparse_dense_len
        # (their queries select each their own blocks); chunk_queries,
        # chunk_keys_scored, chunk_rows_attended: the same three of the
        # chunks' real queries, whatever their program. The last two come
        # from the device (ops/sparse_blocks.CHUNK_STATS, summed over the
        # sparse layers): tiles of the chunks' masked attention that were
        # attended, and those no query of the chunk had selected
        self.sparse: Dict[str, int] = dict.fromkeys(
            ("decode_rows", "dense_rows", "keys_scored", "blocks_selected",
             "blocks_forced", "rows_attended", "rows_in_context",
             "chunk_calls", "chunk_queries", "chunk_keys_scored",
             "chunk_rows_attended") + CHUNK_STATS, 0)
        # all zero for a model whose expert layers do not count
        self.moe: Dict[str, int] = dict.fromkeys(MOE_STATS, 0)
        self._moe_pending: list = []
        self._moe_lock = threading.Lock()

    def observe_decode_attention(self, contexts, steps: int,
                                 window: Optional[int] = None,
                                 sink: bool = False) -> None:
        """A fused window of `steps` decode steps over sequences whose
        contexts (tokens in the cache, the one being decoded included)
        are `contexts` at its first step. `window`: None = a model of one
        kind (`attn`); else both kinds of `attn_kinds`, a sliding layer's
        query reading min(context, window) rows (0: full layers alone, a
        hybrid model's). `sink`: the sliding layers' softmax carries one."""
        if window is not None:
            ctx = (np.asarray(list(contexts), np.int64)[:, None]
                   + np.arange(steps, dtype=np.int64)[None, :])
            for kind in ("full", "window") if window else ("full",):
                rows = ctx if kind == "full" else np.minimum(ctx, window)
                a = self.attn_kinds[kind]
                a["decode_q_rows"] += int(ctx.size)
                a["decode_kv_rows"] += int(rows.sum())
            if window and sink:
                self.attn_kinds["window"]["sink_rows"] += int(ctx.size)
            return
        a = self.attn
        a["decode_q_rows"] += len(contexts) * steps
        a["decode_kv_rows"] += (sum(contexts) * steps
                                + len(contexts) * steps * (steps - 1) // 2)

    def observe_mixed_attention(self, contexts, start: int, take: int,
                                block_q: int = 8,
                                window: Optional[int] = None,
                                sink: bool = False) -> None:
        """One ragged step: decode rows as above, and `take` tokens of a
        prompt from position `start` (`window` as above: a chunk token at
        position p reads min(p + 1, window) rows, a query block the rows
        from its first token's reach to its last token; `sink` as above)."""
        if window is not None:
            ctx = np.asarray(list(contexts), np.int64)
            if window and sink:
                self.attn_kinds["window"]["sink_rows"] += int(ctx.size) + take
            pos = start + np.arange(take, dtype=np.int64)
            first = pos[::block_q]  # each block's first token
            last = np.minimum(first + block_q, start + take)  # horizon
            for kind in ("full", "window") if window else ("full",):
                w = None if kind == "full" else window
                a = self.attn_kinds[kind]
                a["mixed_decode_q_rows"] += int(ctx.size)
                a["mixed_decode_kv_rows"] += int(
                    (ctx if w is None else np.minimum(ctx, w)).sum())
                a["mixed_chunk_q_rows"] += take
                a["mixed_chunk_kv_pairs"] += int(
                    (pos + 1 if w is None
                     else np.minimum(pos + 1, w)).sum())
                a["mixed_chunk_block_kv_rows"] += int(
                    (last if w is None
                     else last - np.maximum(first - (w - 1), 0)).sum())
            return
        a = self.attn
        a["mixed_decode_q_rows"] += len(contexts)
        a["mixed_decode_kv_rows"] += sum(contexts)
        a["mixed_chunk_q_rows"] += take
        a["mixed_chunk_kv_pairs"] += take * start + take * (take + 1) // 2
        blocks = -(-take // block_q)
        a["mixed_chunk_block_kv_rows"] += (
            blocks * start + block_q * blocks * (blocks + 1) // 2)

    def observe_dsa(self, topk: int, selects: bool, contexts=(),
                    steps: int = 1, chunk=None) -> None:
        """One dispatch of an indexed model: decode rows whose contexts
        (the token being decoded included) are `contexts` at the first of
        `steps` steps, and `chunk` = (start, take) prompt tokens. `selects`:
        whether that program runs the selection."""
        d = self.dsa
        ctx = (np.asarray(list(contexts), np.int64)[:, None]
               + np.arange(steps, dtype=np.int64)[None, :]).reshape(-1)
        d["decode_queries"] += int(ctx.size)
        both = [ctx]
        if chunk is not None:
            start, take = chunk
            pctx = start + 1 + np.arange(take, dtype=np.int64)
            d["chunk_queries"] += int(take)
            both.append(pctx)
            if selects:
                d["chunk_keys_scored"] += int(pctx.sum())
        if selects:
            d["decode_keys_scored"] += int(ctx.sum())
        for c in both:
            d["queries_unselected"] += int((c <= topk).sum())
            if selects:
                d["rows_selected"] += int(np.minimum(c, topk).sum())

    def observe_sparse(self, sz, contexts=(), steps: int = 1,
                       chunk=None) -> None:
        """One dispatch of a model with block-sparse attention layers whose
        decode table can hold a context past `sz.dense_len` (`sz`:
        ops/sparse_blocks.Sizes): decode rows whose contexts (the token
        being decoded included) are `contexts` at the first of `steps`
        steps, and `chunk` = (start, take) prompt tokens."""
        d = self.sparse

        def attended(ctx):
            """(rows past dense_len, KV rows all of `ctx` attend a head)."""
            far = ctx[ctx > sz.dense_len]
            return far, int(ctx.sum() - far.sum() + (
                (sz.picked - 1) * sz.block + far
                - (far - 1) // sz.block * sz.block).sum())

        ctx = (np.asarray(list(contexts), np.int64)[:, None]
               + np.arange(steps, dtype=np.int64)[None, :]).reshape(-1)
        far, rows = attended(ctx)
        d["decode_rows"] += int(ctx.size)
        d["dense_rows"] += int(ctx.size - far.size)
        d["rows_in_context"] += int(ctx.sum())
        d["keys_scored"] += int((far // sz.stride - 1).sum())
        d["blocks_selected"] += int(far.size) * sz.picked
        d["blocks_forced"] += int(far.size) * (sz.init_blocks
                                               + sz.window_blocks)
        d["rows_attended"] += rows
        if chunk is not None:
            start, take = chunk
            far, rows = attended(start + 1 + np.arange(take, dtype=np.int64))
            d["chunk_calls"] += int(start + take > sz.dense_len)
            d["chunk_queries"] += int(take)
            d["chunk_keys_scored"] += int((far // sz.stride - 1).sum())
            d["chunk_rows_attended"] += rows

    def observe_ssm(self, decode_rows: int, steps: int,
                    chunk_tokens: int = 0, slots_touched: int = 0,
                    conv: bool = False) -> None:
        """One dispatch of a hybrid model: `decode_rows` live rows over
        `steps` steps, each step's update touching `slots_touched` state
        slots, and `chunk_tokens` of a prompt (0: no chunk); counted under
        `ssm`, or under `conv` where the state layers are short
        convolutions (ModelConfig.conv_state)."""
        s = self.conv if conv else self.ssm
        s["decode_rows"] += decode_rows * steps
        s["layer_steps"] += steps
        s["slots_touched"] += slots_touched * steps
        if chunk_tokens:
            s["chunk_tokens"] += chunk_tokens
            s["chunk_calls"] += 1

    def observe_moe(self, stats, into: str = "moe") -> None:
        """One program's expert-layer counts (a device array, maybe still
        being computed); `into` "sparse": its sparse layers' CHUNK_STATS."""
        with self._moe_lock:
            self._moe_pending.append((into, stats))
            if len(self._moe_pending) < 256:
                return
            # the oldest finished long ago: reading them cannot wait
            done = self._moe_pending[:-8]
            del self._moe_pending[:-8]
        self._fold_moe(done)

    def _fold_moe(self, done: list) -> None:
        """Read the counts back (outside the lock: the newest may still be
        computed, and the step loop must not queue behind a reader)."""
        by: Dict[str, list] = {}
        for into, st in done:
            by.setdefault(into, []).append(np.asarray(st))
        with self._moe_lock:
            for into, counted in by.items():
                target = getattr(self, into)
                names = MOE_STATS if into == "moe" else CHUNK_STATS
                for key, v in zip(names, np.sum(counted, axis=0,
                                                dtype=np.int64)):
                    target[key] += int(v)

    def observe_first_token(self, submit_s: float, queue_s: float,
                            prefill_s: float, emit_s: float) -> None:
        with self._first_token_lock:
            ft = self.first_token
            ft["count"] += 1
            ft["submit_s"] += submit_s
            ft["queue_s"] += queue_s
            ft["prefill_s"] += prefill_s
            ft["emit_s"] += emit_s
            ft["ttft_s"] += submit_s + queue_s + prefill_s + emit_s

    def observe_phase(self, phase: str, seconds: float,
                      weight: int = 1) -> None:
        self.phases[phase].observe(seconds, weight)

    def observe_occupancy(self, active: int, capacity: int) -> None:
        """One decode window's batch occupancy fraction."""
        frac = active / max(capacity, 1)
        self._bucketize(self._OCC_EDGES, self.occupancy_buckets, frac)
        self.occupancy_sum += frac
        self.occupancy_count += 1

    def observe_spec_accept(self, n_acc: int,
                            drafter: Optional[str] = None) -> None:
        """One speculating slot's accepted-draft count for one verify step
        (same cumulative-bucket scheme as occupancy). `drafter` also files
        the observation under that proposer's labeled series."""
        self._bucketize(self._SPEC_EDGES, self.spec_accept_buckets, n_acc)
        self.spec_accept_sum += n_acc
        self.spec_accept_count += 1
        if drafter is not None:
            hist = self.spec_hist_by.setdefault(
                drafter, [0] * (len(self._SPEC_EDGES) + 1))
            self._bucketize(self._SPEC_EDGES, hist, n_acc)
            self.spec_sum_by[drafter] = (
                self.spec_sum_by.get(drafter, 0) + n_acc)
            self.spec_count_by[drafter] = (
                self.spec_count_by.get(drafter, 0) + 1)

    @staticmethod
    def _bucketize(edges, buckets: List[int], x) -> None:
        """Count `x` in the first bucket whose edge holds it (the last
        bucket takes what passes every edge)."""
        buckets[bisect.bisect_left(edges, x)] += 1

    def add_spec_tokens(self, drafted: int, accepted: int,
                        drafter: Optional[str] = None) -> None:
        """One verify dispatch's draft/accept token totals."""
        self.spec_draft_tokens += drafted
        self.spec_accepted_tokens += accepted
        if drafter is not None:
            self.spec_draft_by[drafter] = (
                self.spec_draft_by.get(drafter, 0) + drafted)
            self.spec_accepted_by[drafter] = (
                self.spec_accepted_by.get(drafter, 0) + accepted)

    def observe_mixed(self, prefill_tokens: int, decode_rows: int) -> None:
        """One unified ragged step's composition: prefill-token fraction
        of the window's total rows (same cumulative-bucket scheme as
        occupancy; the exposition bridge serves both as histograms)."""
        frac = prefill_tokens / max(prefill_tokens + decode_rows, 1)
        self._bucketize(self._OCC_EDGES, self.mixed_buckets, frac)
        self.mixed_sum += frac
        self.mixed_count += 1

    def reset_phases(self, *names: str) -> None:
        """Re-zero selected phase histograms (bench section boundaries)."""
        for n in names:
            self.phases[n] = PhaseTimer()

    def kernel_counters(self) -> Dict[str, dict]:
        """What the kernels were asked for (attn, attn_kinds, dsa, moe, ssm)
        and what admission lacked (admit_blocked), as snapshot() gives them
        and alone: a profiler capture samples these a few times a second
        (serving/api.py capture_trace)."""
        with self._moe_lock:
            done, self._moe_pending = self._moe_pending, []
        self._fold_moe(done)
        return {"attn": dict(self.attn),
                "attn_kinds": {k: dict(v)
                               for k, v in self.attn_kinds.items()},
                "dsa": dict(self.dsa), "moe": dict(self.moe),
                "sparse": dict(self.sparse),
                "ssm": dict(self.ssm), "conv": dict(self.conv),
                "admit_blocked": dict(self.admit_blocked)}

    def snapshot(self) -> Dict[str, float]:
        out = {k: v for k, v in self.__dict__.items()
               if k not in ("phases", "occupancy_buckets", "mixed_buckets",
                            "spec_accept_buckets", "spec_draft_by",
                            "spec_accepted_by", "spec_hist_by",
                            "spec_sum_by", "spec_count_by",
                            "first_token", "_first_token_lock", "moe", "attn",
                            "attn_kinds", "dsa", "sparse", "ssm", "conv",
                            "admit_blocked",
                            "_moe_pending", "_moe_lock")}
        out.update(self.kernel_counters())
        with self._first_token_lock:
            out["first_token"] = dict(self.first_token)
        out["phases"] = {p: t.snapshot() for p, t in self.phases.items()}
        out["spec_accept_mean"] = (
            round(self.spec_accept_sum / self.spec_accept_count, 4)
            if self.spec_accept_count else 0.0)
        out["spec_by_drafter"] = {
            d: {
                "draft_tokens": self.spec_draft_by.get(d, 0),
                "accepted_tokens": self.spec_accepted_by.get(d, 0),
                "acceptance_rate": (
                    round(self.spec_accepted_by.get(d, 0)
                          / self.spec_draft_by[d], 4)
                    if self.spec_draft_by.get(d) else 0.0),
                "accept_mean": (
                    round(self.spec_sum_by.get(d, 0)
                          / self.spec_count_by[d], 4)
                    if self.spec_count_by.get(d) else 0.0),
            }
            for d in sorted(set(self.spec_draft_by)
                            | set(self.spec_count_by))}
        out["mixed_frac_mean"] = (
            round(self.mixed_sum / self.mixed_count, 4)
            if self.mixed_count else 0.0)
        return out


class InflightPrefill:
    """A long prompt being prefilled chunk-by-chunk between decode windows."""

    __slots__ = ("req", "pages", "pages_arr", "prompt_len", "done", "slot",
                 "t_start", "aslot", "key")

    def __init__(self, req: GenRequest, pages, pages_arr, prompt_len: int,
                 slot: int, key, aslot: int = 0):
        self.req = req
        # the request's PRNG chain root, drawn at admission: by the final
        # chunk it is computed, and reading it waits on nothing in flight
        self.key = key
        self.pages = pages  # real page ids (host list, allocator-owned)
        self.pages_arr = pages_arr  # bucket-padded np.int32 for the jit
        self.prompt_len = prompt_len
        self.done = 0  # tokens whose KV is cached so far
        self.t_start = time.monotonic()  # admission time (TTFT accounting)
        self.slot = slot  # decode slot RESERVED at admission (a concurrent
        # import_kv taking the last slot mid-prefill would strand the finish)
        self.aslot = aslot  # LoRA device slot (pins it against eviction
        # for the chunks' duration; the registry reads it)


class FirstRow(NamedTuple):
    """A mixed step's operand for its prompt's first token (mixed_fn): the
    program samples it from the chunk's last-row logits as sample_first
    does and, under `join`, writes the row into the device carry at
    `slot`. Every chunk's program takes one; it means something under the
    prompt's final chunk."""

    slot: jax.Array  # int32: the decode slot reserved at _start_inflight
    join: jax.Array  # bool: install the row (tokens, positions, context
    # lengths, the count row); False: sample only
    key: jax.Array  # uint32 [2]: the request's PRNG chain root
    # (temperature, top_p, top_k, min_p, bias_ids, bias_vals), one lane
    # each: Engine._first_sampling
    sampling: tuple


class PendingProgram(NamedTuple):
    """The program in flight under async scheduling: dispatched over the
    decode batch and not read back yet, a fused window or a mixed step
    alike. The next program is dispatched on its device outputs (the carry
    in Engine._dev_state, the pools) before _materialize_window reads it.
    A sequence that ends in it, or is found ended when the program before
    it is read, does not make the engine read it early: the slot is
    retired in the carry (Engine._finish_slot), and what this program may
    still touch of the leaver (pages, ring, decode slot) waits in
    Engine._held under `ticket` until _materialize_window has read it.
    Nor does a prompt that ends in it: a mixed step that carries a
    prompt's final chunk samples the first token and writes the
    newcomer's row into the carry itself (Engine._join), and the host
    learns the token when it reads the program, one program late."""

    lag: int  # decode steps it advances every slot: what the host lags by
    ys: tuple  # tokens (and logprobs) still on the device
    want_lp: bool
    dispatch_s: float  # HOST dispatch cost; the readback adds its own wait
    slots: List[int]  # membership AT DISPATCH
    ticket: int  # timeline.dispatch_seq after the dispatch
    # a mixed step's alone: (start, take) of the prompt's chunk, and the
    # decode rows' contexts as the kernels were handed them
    chunk: Optional[Tuple[int, int]] = None
    contexts: Optional[List[int]] = None
    # of a mixed step that carries a prompt's final chunk and stays in
    # flight: the prompt, and its first token as the program sampled it
    # (token, logprob triple, the sentinel's isfinite), still on the device
    joiner: Optional[InflightPrefill] = None
    first: Optional[tuple] = None


class Engine:
    """Single-replica engine: owns params, KV pages, and the batching loop."""

    def __init__(
        self,
        cfg: EngineConfig,
        model_cfg: Optional[ModelConfig] = None,
        params=None,
        devices=None,
    ):
        """`devices`: optional explicit device list for this engine's mesh —
        disaggregated roles colocated on one slice place prefill and decode
        on DISJOINT sub-meshes of the same host this way (None = the
        process-global jax.devices(), the single-role default)."""
        self.cfg = cfg
        if cfg.speculative_mode != "off":
            # fail fast with the constraint, not a downstream shape error:
            # K bounds the verify window (the ragged verify row must fit
            # one padded query block, so K+1 <= page_size; see
            # ops/ragged_attention.py) and the proposer needs >= 1 pattern
            # token
            k = cfg.num_speculative_tokens
            if k <= 0:
                raise ValueError(
                    f"--num-speculative-tokens must be >= 1 when "
                    f"--speculative-mode is on (got {k})")
            if k >= cfg.page_size:
                raise ValueError(
                    f"--num-speculative-tokens ({k}) must be < --page-size "
                    f"({cfg.page_size}): the K+1-token verify window must "
                    f"fit one KV page / ragged query block")
            if cfg.ngram_lookup < 1:
                raise ValueError(
                    f"--ngram-lookup must be >= 1 (got {cfg.ngram_lookup})")
            if cfg.drafter not in ("ngram", "model"):
                raise ValueError(
                    f"--drafter must be 'ngram' or 'model' (got "
                    f"{cfg.drafter!r})")
            if ("model" in (cfg.speculative_mode, cfg.drafter)
                    and cfg.resolved_draft_pages() < k + 1):
                raise ValueError(
                    f"--draft-num-pages ({cfg.resolved_draft_pages()}) must "
                    f"be >= K+1 ({k + 1}): one verify window drafts K "
                    f"tokens plus the bonus position and must fit the "
                    f"draft pool even before its LRU arm can shed slots")
        backend = jax.default_backend()
        default_dtype = "float32" if backend == "cpu" else "bfloat16"
        if model_cfg is None:
            model_cfg = ModelConfig.from_model_name(
                cfg.model_path or cfg.model, dtype=cfg.dtype or default_dtype
            )
        self.model_cfg = model_cfg
        if model_cfg.is_dsa and (cfg.speculative_mode != "off"
                                 or cfg.sequence_parallel > 1):
            raise ValueError(
                "a model under the learned sparse selection (index_topk="
                f"{model_cfg.index_topk}) is served without speculation and "
                "without sequence parallelism: verify windows and the "
                "ring / Ulysses prefill have no per-query selection "
                "(models/llama.py, ops/attention.dsa_*)")
        if model_cfg.layer_types:
            # layers of more than one kind: a KV pool and a page table for
            # each kind (engine/kv_cache.py). What has no second table or
            # no turned ring yet refuses here, by name, not downstream
            unserved = [name for name, on in (
                ("speculation", cfg.speculative_mode != "off"),
                ("sequence parallelism", cfg.sequence_parallel > 1),
                ("LoRA adapters", cfg.lora_slots > 0),
                ("the KVBM host tier", cfg.kvbm_host_blocks > 0),
                ("disaggregated prefill / decode (the KV transfer carries "
                 "one pool)", cfg.disaggregation_mode != "agg"),
                ("whole-prompt prefill (set --prefill-chunk-tokens or "
                 "--mixed-batch-tokens)",
                 cfg.prefill_chunk_tokens <= 0
                 and cfg.mixed_batch_tokens <= 0),
            ) if on]
            if unserved:
                raise ValueError(
                    "a model whose layers are of more than one kind "
                    f"(layer_types) is not served with: {'; '.join(unserved)}")
        if model_cfg.mixer_types:
            # a hybrid model: a state slot a sequence beside its pages
            # (engine/kv_cache.py). What would have to carry, split or roll
            # back a state refuses here, by name
            unserved = [name for name, on in (
                ("speculation (a verify window would have to roll a state "
                 "back)", cfg.speculative_mode != "off"),
                ("sequence parallelism", cfg.sequence_parallel > 1),
                ("tensor parallelism (heads and groups of a state split "
                 "over a model axis)", cfg.tensor_parallel > 1),
                ("an int8 KV cache", cfg.kv_cache_dtype == "int8"),
                ("LoRA adapters", cfg.lora_slots > 0),
                ("the KVBM host tier (a block carries no state)",
                 cfg.kvbm_host_blocks > 0),
                ("disaggregated prefill / decode (the KV transfer carries "
                 "no state)", cfg.disaggregation_mode != "agg"),
            ) if on]
            if unserved:
                raise ValueError(
                    "a hybrid model (mixer_types: state-space layers) is "
                    f"not served with: {'; '.join(unserved)}")
            if model_cfg.is_sala:
                sparse_blocks.check_page_size(model_cfg, cfg.page_size)
        if cfg.sequence_parallel > 1:
            # long-context serving: prefill shards the sequence over the
            # `seq` axis (ring/Ulysses over ICI); params/KV shard on
            # `model` as usual and replicate over `seq`; the paged decode
            # ops exclude seq meshes and run GSPMD on the same mesh
            if cfg.data_parallel > 1 or cfg.expert_parallel > 1:
                raise ValueError(
                    "sequence_parallel composes with tensor_parallel only "
                    "(set --dp/--ep to 1)")
            if (model_cfg.sliding_window > 0
                    or model_cfg.attn_logit_softcapping > 0):
                raise ValueError(
                    "sequence_parallel does not support sliding-window/"
                    "softcap (gemma-2-family) models yet — the ring/Ulysses "
                    "prefill has neither a window mask nor score capping")
            # fail fast on a bad strategy: the env var is read at trace
            # time inside the jitted prefill (baked into the compiled
            # executable — a process-start setting, not a live knob), so
            # without this check a typo would 500 the first request
            import os as _os

            strategy = _os.environ.get("DYNAMO_TPU_SP_STRATEGY", "ring")
            if strategy not in ("ring", "ulysses"):
                raise ValueError(
                    f"DYNAMO_TPU_SP_STRATEGY {strategy!r} not in "
                    f"('ring', 'ulysses')")
            from dynamo_tpu.parallel.mesh import build_long_context_mesh

            self.mesh = build_long_context_mesh(
                cfg.sequence_parallel, cfg.tensor_parallel, devices=devices)
        else:
            self.mesh = build_mesh(
                MeshConfig(
                    tensor_parallel=cfg.tensor_parallel,
                    data_parallel=cfg.data_parallel,
                    expert_parallel=cfg.expert_parallel,
                ),
                devices=devices,
            )
        self.metrics = EngineMetrics()
        self._lock = threading.Lock()
        # serialises every computation that touches the donated KV pools
        # (step() on the scheduler thread vs prefill_only/export_kv/import_kv
        # on HTTP threads in disaggregated roles)
        self._exec_lock = threading.RLock()

        # --- parameters ---
        if params is None:
            from dynamo_tpu.models.loader import load_or_init_params

            params = load_or_init_params(
                self.model_cfg, cfg.model_path, seed=cfg.seed,
                quantization=cfg.quantization,
            )
        with self.mesh:
            self.params = shd.shard_params(params, self.mesh)
        # live elasticity (dynamo_tpu/elasticity): the weight-version
        # pointer. Every jitted program takes params as a per-call operand,
        # so a staged tree with identical leaves flips in between steps
        # (under _exec_lock) with zero recompiles; _kv_namespace seeds all
        # KV hashing with the active version so v1 blocks never verify
        # against v2 weights.
        from dynamo_tpu.elasticity.weights import WeightManager

        self.weights = WeightManager(self, version=cfg.model_version)

        # --- KV cache ---
        # int8 rows are lane-blocked per TP shard (KVCacheSpec.lane_blocks),
        # so the fused lane axis shards cleanly and the Pallas decode/chunk
        # kernels dequantize in-VMEM after the superblock DMA
        ps = cfg.page_size
        self.kv_spec = KVCacheSpec.from_model(
            self.model_cfg, cfg.num_pages, cfg.page_size,
            kv_dtype=cfg.kv_cache_dtype,
            tensor_parallel=cfg.tensor_parallel,
            # pools by kind: a ring for every slot and one to spare; a step
            # writes a chunk ahead, or two decode windows (one in flight)
            window_slots=cfg.max_num_seqs + 1,
            window_ahead=max(
                -(-max(cfg.prefill_chunk_tokens, cfg.mixed_batch_tokens)
                  // ps) * ps,
                2 * max(1, cfg.num_scheduler_steps) + ps),
            # a hybrid model: a state slot a decode slot
            state_slots=cfg.max_num_seqs,
            # block-sparse layers: a row of key sums a page of the widest
            # table a sequence's chunks are handed
            pooled_key_pages=cfg.max_pages_per_seq + att_ops.chunk_table_tail(
                max(cfg.prefill_chunk_tokens, cfg.mixed_batch_tokens),
                cfg.page_size) + 1,
        )
        # MLA pools replicate across the model axis (every TP shard scores
        # its local heads against the FULL shared latent row); classic
        # pools lane-split the fused per-head axis
        self.k_pages, self.v_pages = alloc_kv_pages(
            self.kv_spec,
            shd.replicated(self.mesh) if self.model_cfg.is_mla
            else shd.kv_sharding(self.mesh),
        )
        self.allocator = PageAllocator(cfg.num_pages)
        # the sliding layers' rings (pools by kind), None with one pool
        self.win_rings: Optional[WindowRings] = None
        if self.kv_spec.window_layers:
            self.win_rings = WindowRings(self.kv_spec.window_pages,
                                         self.kv_spec.ring_pages)
        # the snapshot pool of a hybrid model whose states are kept at
        # block boundaries (_new_prefix_cache): (k_pages.state,
        # v_pages.state) with an entry axis in front of each slot's rows
        self._state_snaps = None
        self.prefix_cache: Optional[PrefixCache] = None
        if cfg.mixed_batch_tokens > 0:
            # the unified ragged step packs prefill-chunk tokens into the
            # same program as the decode rows, so the budget must be
            # page-aligned for the same whole-page KV-scatter reason as
            # prefill_chunk_tokens below. Mixed mode IMPLIES chunked
            # prefill (the packed tokens ARE chunks): an unset chunk size
            # inherits the mixed budget so both paths agree on chunk
            # geometry and the A/B bench compares scheduling, not shapes.
            mixed = -(-cfg.mixed_batch_tokens
                      // cfg.page_size) * cfg.page_size
            chunk = cfg.prefill_chunk_tokens or mixed
            if (mixed != cfg.mixed_batch_tokens
                    or chunk != cfg.prefill_chunk_tokens):
                cfg = dataclasses.replace(cfg, mixed_batch_tokens=mixed,
                                  prefill_chunk_tokens=chunk)
                self.cfg = cfg
        if cfg.sequence_parallel > 1 and cfg.prefill_chunk_tokens > 0:
            # chunked prefill routes through the paged chunk op, which the
            # ring/Ulysses path does not serve — a long-context sp worker
            # exists precisely for whole-prompt ring prefills
            log.warning(
                "sequence_parallel=%d disables chunked prefill (ring "
                "attention serves whole-prompt prefills)",
                cfg.sequence_parallel)
            cfg = dataclasses.replace(cfg, prefill_chunk_tokens=0,
                              mixed_batch_tokens=0)
            self.cfg = cfg
        # prefix caching historically required chunked prefill (cache hits
        # re-enter as mid-prompt chunks); the ragged mixed step serves the
        # same mid-prompt shapes, so either path lifts the exclusion
        if cfg.enable_prefix_caching and (cfg.prefill_chunk_tokens > 0
                                          or cfg.mixed_batch_tokens > 0):
            self._new_prefix_cache()
        # a cached prefix is counted and never served where a sequence holds
        # something of its own beside its pages: a sliding layer's ring is
        # not shared, and a prefix hit of a hybrid model needs the state at
        # the block. Unless that state is kept there: one that costs no
        # more than the block's own KV (LFM2's two conv rows a layer, 147 KB
        # against a page's 197 KB) is saved beside the cached pages at
        # every boundary a chunk ends on and a hit is served from the
        # deepest (_save_state, _restore_state); a Mamba-2 state of
        # megabytes (Nemotron-H 8.5 MB, Falcon-H1 4.2 MB, against pages of
        # 0.1-0.4 MB) is kept at no boundary, so those models recompute
        self._prefix_recomputed = (
            (self.win_rings is not None or bool(self.model_cfg.mixer_types))
            and self._state_snaps is None)
        # KVBM tiered block manager: evicted prefix pages demote to a
        # bounded host-RAM pool (and optionally disk) instead of dying;
        # lookups onboard them back, cost-gated (dynamo_tpu.kvbm)
        self.kvbm = None
        if self.prefix_cache is not None and cfg.kvbm_host_blocks > 0:
            from dynamo_tpu.kvbm.manager import KVBM

            self.kvbm = KVBM(self)
            self.prefix_cache.kvbm = self.kvbm
            log.info(
                "kvbm host tier: %d blocks x %d bytes (%.1f MiB host RAM), "
                "gate=%s%s", cfg.kvbm_host_blocks,
                self.kvbm.pool.block_nbytes,
                cfg.kvbm_host_blocks * self.kvbm.pool.block_nbytes / 2**20,
                cfg.kvbm_gate,
                f", disk tier at {cfg.kvbm_disk_dir}"
                if cfg.kvbm_disk_dir else "")

        # --- multi-LoRA adapter serving (dynamo_tpu.lora) ---
        # the registry installs stacked [L, slots+1, in, rank] adapter
        # tensors into self.params (slot 0 = the all-zero base slot) and
        # manages host-store registration + LRU device loads; every jit
        # signature gains per-sequence slot indices ONLY when enabled
        self.lora = None
        if cfg.lora_slots > 0:
            from dynamo_tpu.lora.registry import LoRARegistry, \
                parse_adapter_list

            self.lora = LoRARegistry(self)
            for name, path in parse_adapter_list(cfg.lora_adapters or ""):
                self.lora.register(name, path=path)
            log.info(
                "multi-LoRA serving: %d device slots x rank<=%d (%s "
                "boot-registered)", cfg.lora_slots, cfg.lora_rank,
                len(self.lora.names()) or "none")

        # --- per-tenant QoS (dynamo_tpu.qos) ---
        # weighted-fair token budgets: each request carries the tenant the
        # serving layer resolved; the accountant debits decoded tokens and
        # credits total throughput by weight share. Over-budget tenants
        # defer admission, lose group widening, and rank first as
        # preemption victims. Disabled (None) without configured tenants —
        # the scheduler then behaves byte-identically to the pre-QoS code.
        from dynamo_tpu.qos.tenancy import TenantAccountant, TenantRegistry

        self.tenant_registry = (TenantRegistry.from_json(cfg.tenants)
                                if cfg.tenants else TenantRegistry.from_env())
        self.qos: Optional[TenantAccountant] = None
        if self.tenant_registry.enabled:
            self.qos = TenantAccountant(
                self.tenant_registry, burst_tokens=cfg.qos_burst_tokens)
            log.info("per-tenant QoS: %d classes, burst %d tokens",
                     len(self.tenant_registry.classes), self.qos.burst)
        # request_id -> tenant, for budget accounting of TokenEvents whose
        # sequence may already be gone by the time step() returns them
        self._rid_tenant: Dict[str, str] = {}

        # flight recorder + cost attribution (observability plane): one
        # ring record per step, one ledger entry per executed segment.
        # Both are lock-cheap enough to stay on unconditionally; the ring
        # size is an env knob (DYNAMO_TPU_FLIGHT_RECORDS, 0 disables).
        from dynamo_tpu.observability.cost import CostLedger
        from dynamo_tpu.observability.flight import FlightRecorder
        from dynamo_tpu.observability.timeline import StepTimeline

        self.flight = FlightRecorder()
        self.cost = CostLedger()
        if self.tenant_registry.enabled:
            # preemptible batch tier: /debug/costs and the heartbeat
            # rollup price the batch lane as its own row next to the
            # per-tenant entries (docs/autoscaling.md chargeback)
            reg = self.tenant_registry
            self.cost.tier_of = (
                lambda t: "batch" if reg.is_batch(t) else "interactive")
        # stepline: precise per-step phase intervals + inter-dispatch
        # host-gap accounting (DYNAMO_TPU_TIMELINE / _TIMELINE_RECORDS)
        self.timeline = StepTimeline()
        # engine watchdog (robustness/watchdog.py): every stepline device
        # phase arms a hang deadline; the health state machine drives
        # shedding, in-place resurrection, and permanent quarantine.
        # Sentinel tier resolved once at construction (env is a boot knob).
        # The derived deadline arms only once warmup() has completed on a
        # real accelerator: until then (and always on the CPU) a seam may
        # hold a compilation. Env/CI overrides trip everywhere.
        self.watchdog = EngineWatchdog(self, derive_deadline=False)
        self.timeline.watch = self.watchdog
        self.integrity = integrity_mode()
        self._page_nbytes = (self.kv_spec.bytes_per_token()
                             * cfg.page_size)
        # pallas/spec demotion counts already seen (per-step delta -> ring)
        self._flight_fallback_prev: Dict[tuple, int] = dict(
            att_ops.pallas_fallback_counts())

        # --- Speculation v3 (dynamo_tpu.speculation) ---
        # drafter_name labels every spec metric sample; the model drafter
        # runs a real second model over its own paged KV pool and the
        # adaptive controller resizes the per-slot window from live
        # acceptance lengths. Proposals feed the SAME verify path either
        # way — what proposes never changes what streams.
        self.drafter_name: Optional[str] = None
        self.draft = None
        self._adaptive = None
        if cfg.speculative_mode != "off":
            self.drafter_name = ("model" if "model" in (cfg.speculative_mode,
                                                        cfg.drafter)
                                 else "ngram")
            if self.drafter_name == "model":
                from dynamo_tpu.speculation import DraftEngine

                self.draft = DraftEngine(self)
            if cfg.spec_adaptive_k:
                from dynamo_tpu.speculation import AdaptiveK

                self._adaptive = AdaptiveK(cfg.num_speculative_tokens)

        # --- batch slots (host-side mirrors of device batch state) ---
        b, pmax = cfg.max_num_seqs, cfg.max_pages_per_seq
        self.block_tables = np.zeros((b, pmax), dtype=np.int32)
        # the slots' rings on the sliding layers (pools by kind)
        self.win_tables = np.zeros((b, self.kv_spec.ring_pages),
                                   dtype=np.int32)
        self.cur_tokens = np.zeros((b,), dtype=np.int32)
        self.positions = np.zeros((b,), dtype=np.int32)
        self.context_lens = np.zeros((b,), dtype=np.int32)  # 0 = inactive
        self.temperature = np.zeros((b,), dtype=np.float32)
        self.top_p = np.ones((b,), dtype=np.float32)
        self.top_k = np.zeros((b,), dtype=np.int32)
        self.presence = np.zeros((b,), dtype=np.float32)
        self.frequency = np.zeros((b,), dtype=np.float32)
        self.min_p = np.zeros((b,), dtype=np.float32)
        # fixed-lane logit_bias packing (smp.BIAS_K per request; -1 = empty)
        self.bias_ids = np.full((b, smp.BIAS_K), -1, dtype=np.int32)
        self.bias_vals = np.zeros((b, smp.BIAS_K), dtype=np.float32)
        # per-slot PRNG chain roots (seeded requests are deterministic
        # regardless of batch composition; see engine/sampling.py)
        self.slot_keys = np.zeros((b, 2), dtype=np.uint32)
        # per-slot LoRA adapter slots (0 = base); uploaded with the
        # sampling state when multi-LoRA serving is enabled
        self.adapter_slots = np.zeros((b,), dtype=np.int32)
        self.seqs: Dict[int, SeqState] = {}
        self._free_slots = list(range(b - 1, -1, -1))
        self.pending: collections.deque[GenRequest] = collections.deque()
        self._inflight: Optional[InflightPrefill] = None
        if cfg.prefill_chunk_tokens > 0:
            # chunks must be page-aligned (chunk KV scatters whole pages);
            # replace rather than mutate the caller's config object
            rounded = -(-cfg.prefill_chunk_tokens
                        // cfg.page_size) * cfg.page_size
            if rounded != cfg.prefill_chunk_tokens:
                cfg = dataclasses.replace(cfg, prefill_chunk_tokens=rounded)
                self.cfg = cfg
        # warmup()'s second pass where no prefix can be served (pools by
        # kind): every prompt takes the chunked path, as a cached one would
        self._warm_chunked = False
        self._aborted: set = set()  # guarded_by: _lock
        # abort_all teardown hook: the serving layer flushes its stream
        # queues here so waiting handles see a final event even when the
        # teardown came from resurrection, not the scheduler loop
        self.on_abort_all: Optional[Callable[[List[str]], None]] = None
        # disagg prefill role: request_id -> (pages, n_tokens) held for export
        self._parked: Dict[str, tuple] = {}

        self.rng = jax.random.PRNGKey(cfg.seed)
        # --- device-resident decode state ---
        # The decode hot loop keeps (cur_tokens, positions, context_lens)
        # and the block-table / sampling arrays on device between windows, so
        # a steady-state window costs ONE dispatch + ONE token download — on
        # networked TPU backends the per-transfer round-trip, not compute, is
        # the decode bottleneck. Host mirrors stay authoritative; any
        # membership/page/sampling mutation invalidates the matching device
        # copy and it is rebuilt from mirrors before the next window.
        self._dev_state = None  # (cur_tokens, positions, context_lens, active)
        self._dev_tables = None
        # (temp, top_p, top_k, pres, freq, min_p, bias_ids, bias_vals, keys)
        self._dev_sampling = None
        self._dev_adapters = None  # [B] int32 LoRA slots (lora mode only)
        # async scheduling: the program (a fused window or a mixed step)
        # dispatched but not read back yet — a PendingProgram
        self._pending_win: Optional[PendingProgram] = None
        # slots retired in the device carry whose sequences are still in
        # `seqs`: their last tokens are in the program in flight, and the
        # program dispatched behind it runs without them (_retire)
        self._leaving: set = set()
        # what a program in flight may still touch of a sequence that was
        # found finished when the program BEFORE it was read: (ticket,
        # pages, request id, decode slot), given back to the allocators
        # and to _free_slots once that ticket has been read (_release_held)
        self._held: List[tuple] = []
        # last warmup() result (programs compiled, seconds) — exposed on
        # worker /metrics by observability/engine_metrics.py
        self.warmup_info = None
        # JSON-guided decoding (ops/json_guide.py): vocab byte table (host +
        # device), lazily-compiled guided window variants, and the
        # device-resident grammar state (gmode, gdepth, gbits, gactive) —
        # invalidated with _dev_state and rebuilt from seq.guide mirrors
        self._guide_table = None
        self._guide_dev = None
        self._guided_windows: Dict = {}
        self._guide_row_cache: Dict = {}
        self._dev_guide = None
        # output-token counts for presence/frequency penalties: [B, V] int32,
        # PERSISTENTLY device-resident (never re-uploaded on membership
        # changes — rows are zeroed in-place by the tiny _reset_count jit)
        self.token_counts = jnp.zeros(
            (b, self.model_cfg.vocab_size), dtype=jnp.int32
        )
        self._build_jit()
        if not cfg.enforce_eager:
            # normalize provenance so the first decode window keys the same
            # compilation as steady state (see _upload)
            (self.token_counts,) = self._upload(self.token_counts)

    def _new_prefix_cache(self) -> None:
        """An empty prefix cache over the allocator and, where the model's
        states are kept at block boundaries
        (KVCacheSpec.state_kept_at_blocks), an empty snapshot pool. A
        chunk ends on a multiple of the chunk width (a hit resumes at one
        too), so an entry for every such boundary the page pool can hold is
        an entry for every snapshot that can be alive: no second LRU, and
        at most 1 / (pages a chunk) of the KV pool's bytes (75 MB beside
        1.6 GB at 8,192 pages of 16 and chunks of 256)."""
        cfg = self.cfg
        entries = 0
        if self.kv_spec.state_kept_at_blocks:
            widths = [-(-c // cfg.page_size) for c in (
                cfg.prefill_chunk_tokens, cfg.mixed_batch_tokens) if c]
            entries = max(1, cfg.num_pages // math.gcd(*widths))
            self._state_snaps = alloc_state_snapshots(
                (self.k_pages.state, self.v_pages.state), entries,
                self.kv_spec.state_slot_axis, shd.replicated(self.mesh))
        self.prefix_cache = PrefixCache(self.allocator, cfg.page_size,
                                        state_entries=entries)

    def _invalidate_dev(self, tables_only: bool = False,
                        keep_carry: bool = False):
        """Mark device copies stale; _ensure_dev_state uploads them again
        from the host's mirrors. `tables_only`: pages were added.
        `keep_carry`: a slot left the batch (_retire, _finish_slot behind a
        program in flight): everything the host owns goes up again (the
        active mask, the tables, the sampling rows, the adapter slots),
        while tokens / positions / context_lens stay the device's own,
        which may be a program ahead of the mirrors."""
        self._dev_tables = None
        if keep_carry:
            self._dev_state = (*self._dev_state[:3], None)
            self._dev_sampling = None
            self._dev_adapters = None
        elif not tables_only:
            self._dev_state = None
            self._dev_sampling = None
            self._dev_guide = None
            self._dev_adapters = None

    # ------------------------------------------------------------------ jit --

    def _build_jit(self):
        cfg, mcfg = self.cfg, self.model_cfg
        page_size = cfg.page_size
        # multi-LoRA serving: when on, every prefill/chunk/window program
        # takes one extra operand (the per-sequence adapter-slot indices).
        # The *aslot splat keeps the lora-off signatures byte-identical to
        # before — no recompiles, no donation-index churn, zero cost.
        lora_on = self.lora is not None
        rep_sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())

        def rep(x):
            """Pin host-readback outputs to fully-replicated: every process
            of a multi-host gang can np.asarray() them locally (a
            GSPMD-chosen batch/vocab sharding would make them
            non-addressable on followers). No-op cost single-process."""
            return jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, rep_sharding), x
            )

        # a model whose expert layers count (llama MixedOut.moe_stats etc.)
        # has every step program return the counts last; `program` takes
        # them off again at each call, so call sites see the same tuples
        # for every model
        # (a minicpm_sala model's sparse layers' CHUNK_STATS ride the same
        # place into metrics.sparse)
        counts_moe = mcfg.moe_grouped or mcfg.is_sala
        n_counts = len(CHUNK_STATS if mcfg.is_sala else MOE_STATS)

        def moe_tail(stats):
            return (rep(stats),) if counts_moe else ()

        def prefill_fn(params, tokens, seq_len, k_pages, v_pages, pages,
                       *aslot):
            out = llama.prefill(
                mcfg, params, tokens, seq_len, k_pages, v_pages, pages,
                page_size=page_size,
                adapter_slots=aslot[0] if aslot else None,
            )
            return (rep(out.last_logits), out.k_pages,
                    out.v_pages) + moe_tail(out.moe_stats)

        def prefill_batch_fn(params, tokens, seq_lens, k_pages, v_pages,
                             pages, *aslot):
            out = llama.prefill_batch(
                mcfg, params, tokens, seq_lens, k_pages, v_pages, pages,
                page_size=page_size,
                adapter_slots=aslot[0] if aslot else None,
            )
            return (rep(out.last_logits), out.k_pages,
                    out.v_pages) + moe_tail(out.moe_stats)

        def sample_first_batch(logits, temperature, top_p, top_k, min_p,
                               bias_ids, bias_vals, keys, positions):
            """First tokens for a batched prefill: [N, V] logits with
            per-lane sampling params and per-request key chains."""
            state = smp.make_state(temperature, top_p, top_k,
                                   min_p=min_p, bias_ids=bias_ids,
                                   bias_vals=bias_vals)
            folded = smp.fold_positions(keys, positions)
            return rep(smp.sample_with_logprobs(logits, state, folded))

        def chunk_fn(params, tokens, start, chunk_len, k_pages, v_pages,
                     pages, *aslot):
            out = llama.prefill_chunk(
                mcfg, params, tokens, start, chunk_len, k_pages, v_pages,
                pages, page_size=page_size,
                adapter_slots=aslot[0] if aslot else None,
            )
            return (rep(out.last_logits), out.k_pages,
                    out.v_pages) + moe_tail(out.moe_stats)

        def make_decode_window(n_steps: int, with_logprobs: bool,
                               guide_tables=None):
            """Up to n_steps fused decode iterations in one dispatch: a loop
            over the step body with on-device sampling AND the batch state
            carried on device, so a steady-state window costs one dispatch +
            one token download instead of ~9 host round-trips. A program of
            n_steps > 1 takes its trip count as its LAST operand, a traced
            int32 `steps` in 1..n_steps (Engine._window_steps: a full window,
            or a short one under the batch's shortest headroom), so one
            program text serves every length;
            rows `steps`.. of its [n_steps, B] results are never written and
            never read. The logprobs
            variant additionally streams back the chosen-token logprob and
            top-5 alternatives per step (compiled lazily — costs nothing
            unless a request asks for logprobs).

            With guide_tables=(token_bytes, token_len, eos_mask) the window
            becomes the JSON-guided variant: three extra int32 [B] args
            carry the grammar automaton state (ops/json_guide.py), each scan
            step masks the logits with the allowed-token set BEFORE sampling
            and folds the sampled token's bytes through the automaton — the
            grammar keeps up with 16/32/64-step fused windows entirely
            on-device (warmup() pre-compiles all four guided variants
            before /ready)."""
            guided = guide_tables is not None
            if guided:
                g_tb, g_tl, g_eos = guide_tables

            def window_fn(
                params, tokens, positions, context_lens, active, block_tables,
                temperature, top_p, top_k, presence, frequency, min_p,
                bias_ids, bias_vals, slot_keys, counts, k_pages, v_pages,
                *extra,
            ):
                # extra layout: [adapter_slots]? + [gmode, gdepth, gbits,
                # gactive]? + [steps]? — adapter slots ride first when lora
                # is on, a fused window's trip count rides last
                steps = 1
                if n_steps > 1:
                    *extra, steps = extra
                gs = extra
                aslots = None
                if lora_on:
                    aslots, gs = extra[0], extra[1:]
                state = smp.SamplingState(
                    temperature, top_p, top_k, presence, frequency,
                    min_p, bias_ids, bias_vals,
                )
                step = active.astype(positions.dtype)  # inactive slots frozen
                b = tokens.shape[0]
                # a hybrid model's live state slots, once for every step
                state_slots = llama.live_state_slots(mcfg, block_tables)
                if guided:
                    gmode0, gdepth0, gbits0, gactive = gs
                    gact = gactive & active

                def draw(logits, keys, cnts):
                    """A step's rows of the results: the sampled tokens [B],
                    and under logprobs the chosen one's and the top-5."""
                    if with_logprobs:
                        return smp.sample_with_logprobs(logits, state, keys,
                                                        cnts)
                    return (smp.sample(logits, state, keys, cnts),)

                def body(carry, _):
                    if guided:
                        toks, pos, ctx_lens, cnts, gm, gd, gb, kp, vp = carry
                    else:
                        toks, pos, ctx_lens, cnts, kp, vp = carry
                    out = llama.decode_step(
                        mcfg, params, toks, pos, block_tables, ctx_lens,
                        kp, vp, page_size=page_size,
                        adapter_slots=aslots, state_slots=state_slots,
                    )
                    logits = out.logits
                    if guided:
                        allow = json_guide.token_mask(
                            jnp, gm, gd, gb, g_tb, g_tl, g_eos)
                        logits = jnp.where(
                            gact[:, None] & ~allow,
                            jnp.asarray(-1e9, logits.dtype), logits)
                    y = draw(logits, smp.fold_positions(slot_keys, pos), cnts)
                    nxt = y[0]
                    # count only active slots' emissions; inactive rows are
                    # zeroed at (re)admission anyway
                    cnts = cnts.at[jnp.arange(b), nxt].add(
                        step.astype(cnts.dtype)
                    )
                    if guided:
                        nm, nd, nb, _ = json_guide.fold_bytes(
                            jnp, gm, gd, gb, g_tb[nxt], g_tl[nxt])
                        gm = jnp.where(gact, nm, gm)
                        gd = jnp.where(gact, nd, gd)
                        gb = jnp.where(gact, nb, gb)
                        new_carry = (nxt, pos + step, ctx_lens + step, cnts,
                                     gm, gd, gb, out.k_pages, out.v_pages)
                    else:
                        # inactive slots stay pinned at position 0 / context
                        # 1 so their trash-page work never grows
                        new_carry = (nxt, pos + step, ctx_lens + step, cnts,
                                     out.k_pages, out.v_pages)
                    return new_carry, (y, out.moe_stats)

                init = ((tokens, positions, context_lens, counts,
                         gmode0, gdepth0, gbits0, k_pages, v_pages)
                        if guided else
                        (tokens, positions, context_lens, counts,
                         k_pages, v_pages))
                # every step writes its row of the preallocated results and
                # adds to the expert layers' counts; the classic program's
                # one trip is a constant. The rows are shaped from the
                # sampler alone, under stand-in logits (the model is traced
                # once, inside the loop): step_at holds every real row to it
                rows_like = jax.eval_shape(
                    draw, jax.ShapeDtypeStruct(counts.shape, jnp.float32),
                    slot_keys, counts)
                ys0 = jax.tree.map(
                    lambda a: jnp.zeros((n_steps,) + a.shape, a.dtype),
                    rows_like)
                st0 = (jnp.zeros((n_counts,), jnp.int32)
                       if counts_moe else None)

                def step_at(i, loop):
                    carry, ys, st = loop
                    carry, (y, st_i) = body(carry, None)
                    assert [(a.shape, a.dtype) for a in y] == [
                        (a.shape, a.dtype) for a in rows_like], y
                    ys = jax.tree.map(
                        lambda rows, row: rows.at[i].set(row), ys, y)
                    return carry, ys, (st + st_i if counts_moe else None)

                carry, ys, st = jax.lax.fori_loop(
                    0, steps, step_at, (init, ys0, st0))
                tail = moe_tail(st)
                if guided:
                    (tokens, positions, context_lens, counts,
                     gm, gd, gb, k_pages, v_pages) = carry
                    # ys: (toks [n_steps, B], [logprob extras...])
                    return (rep(ys), tokens, positions, context_lens, counts,
                            gm, gd, gb, k_pages, v_pages) + tail
                tokens, positions, context_lens, counts, k_pages, v_pages = carry
                return (rep(ys), tokens, positions, context_lens, counts,
                        k_pages, v_pages) + tail

            return window_fn

        n_multi = max(1, cfg.num_scheduler_steps)
        window_fns = {(multi, lp): make_decode_window(
            n_multi if multi else 1, lp)
            for multi in (False, True) for lp in (False, True)}

        def first_token(logits, temperature, top_p, top_k, min_p,
                        bias_ids, bias_vals, req_key, pos):
            """First-token sampling after prefill: logits [V] for one request.
            Penalties don't apply (no output yet) but logit_bias and min_p
            do; logprobs always computed (one [V] row — negligible). ONE
            function for the host's program (sample_first) and for the
            mixed step that samples its own final chunk: same key, same
            position, same bits."""
            state = smp.make_state(temperature, top_p, top_k, min_p=min_p,
                                   bias_ids=bias_ids, bias_vals=bias_vals)
            key = jax.random.fold_in(req_key, pos)
            toks, chosen, tids, tvals = smp.sample_with_logprobs(
                logits[None], state, key[None]
            )
            return toks[0], chosen[0], tids[0], tvals[0]

        def make_mixed_step(with_logprobs: bool):
            """One unified ragged step (RPA, PAPERS.md arxiv 2604.15464):
            every decode slot advances ONE token while up to
            mixed_batch_tokens of the inflight prefill chunk ride the SAME
            program — llama.mixed_step routes both row kinds through
            ragged_mixed_attention, so a long admission stops preempting
            decode ITL. The leading 18 operands match window_fn exactly;
            the chunk operands trail and are fresh uploads each call."""

            def mixed_fn(
                params, tokens, positions, context_lens, active, block_tables,
                temperature, top_p, top_k, presence, frequency, min_p,
                bias_ids, bias_vals, slot_keys, counts, k_pages, v_pages,
                *extra,
            ):
                # extra layout: [adapter_slots]? + (p_tokens, p_start,
                # p_len, p_pages, p_first) + [p_adapter_slot]? — decode
                # adapter slots ride first when lora is on, like the windows
                aslots = None
                if lora_on:
                    aslots, extra = extra[0], extra[1:]
                p_tokens, p_start, p_len, p_pages, p_first = extra[:5]
                p_aslot = extra[5] if lora_on else None
                state = smp.SamplingState(
                    temperature, top_p, top_k, presence, frequency,
                    min_p, bias_ids, bias_vals,
                )
                step = active.astype(positions.dtype)
                b = tokens.shape[0]
                out = llama.mixed_step(
                    mcfg, params, tokens, positions, block_tables,
                    context_lens, p_tokens, p_start, p_len, p_pages,
                    k_pages, v_pages, page_size=page_size,
                    adapter_slots=aslots, chunk_adapter_slot=p_aslot,
                )
                # decode rows sample exactly like a 1-step window: same
                # fold_in(slot_key, position) chain, same count update —
                # token identity vs the classic path is by construction
                keys = smp.fold_positions(slot_keys, positions)
                if with_logprobs:
                    nxt, chosen, tids, tvals = smp.sample_with_logprobs(
                        out.logits, state, keys, counts
                    )
                    y = (nxt[None], chosen[None], tids[None], tvals[None])
                else:
                    nxt = smp.sample(out.logits, state, keys, counts)
                    y = (nxt[None],)
                counts = counts.at[jnp.arange(b), nxt].add(
                    step.astype(counts.dtype)
                )
                # the prompt's first token, sampled where its logits are
                # (under the final chunk the last row IS the prompt's last
                # position) with the host path's own function, and the
                # integrity sentinel beside it. Under `join` the row goes
                # into the carry as _install_slot's rebuild would upload
                # it and reset_count_fn would count it, so the program
                # dispatched behind this one decodes the newcomer
                n_prompt = p_start + p_len
                first = first_token(out.chunk_logits, *p_first.sampling,
                                    p_first.key, n_prompt - 1)
                finite = jnp.isfinite(out.chunk_logits).all()
                slot, join = p_first.slot, p_first.join

                def put(row, new):
                    return row.at[slot].set(jnp.where(join, new, row[slot]))

                counts = put(counts, jnp.zeros_like(counts[slot]).at[
                    first[0]].add(1))
                # chunk_logits go back raw too: the host samples from them
                # where the request keeps the read-at-once order
                # (Engine._joins; same tail as chunk_fn)
                return (rep(y), rep(out.chunk_logits), rep((*first, finite)),
                        put(nxt, first[0]),
                        put(positions + step, n_prompt),
                        put(context_lens + step, n_prompt + 1), counts,
                        out.k_pages, out.v_pages) + moe_tail(out.moe_stats)

            return mixed_fn

        mixed_fns = {lp: make_mixed_step(lp) for lp in (False, True)}

        def _spec_accept(logits, drafts, tokens, positions, context_lens,
                         active, state, slot_keys, counts, room):
            """Shared acceptance tail of the two verify programs: replay
            the per-position sampling chain (smp.verify_accept), bank the
            emitted tokens into the penalty counts, and advance the carried
            batch state by n_acc + 1 per active slot. Penalized slots are
            ineligible (their counts snapshot goes stale mid-window) but
            still emit their exact position-0 token."""
            b, k = drafts.shape
            k1 = k + 1
            eligible = ((state.presence_penalty == 0.0)
                        & (state.frequency_penalty == 0.0) & room & active)
            emitted, n_acc = smp.verify_accept(
                logits, drafts, state, slot_keys, positions, eligible,
                counts)
            emit_mask = ((jnp.arange(k1)[None, :] <= n_acc[:, None])
                         & active[:, None])
            rows = jnp.repeat(jnp.arange(b), k1)
            counts = counts.at[rows, emitted.reshape(-1)].add(
                emit_mask.reshape(-1).astype(counts.dtype)
            )
            step = jnp.where(active, n_acc + 1, 0).astype(positions.dtype)
            last = jnp.take_along_axis(emitted, n_acc[:, None], axis=1)[:, 0]
            tokens_new = jnp.where(active, last, tokens)
            return (emitted, n_acc, tokens_new, positions + step,
                    context_lens + step, counts)

        def spec_fn(params, tokens, drafts, positions, context_lens, active,
                    block_tables, temperature, top_p, top_k, presence,
                    frequency, min_p, bias_ids, bias_vals, slot_keys, counts,
                    room, k_pages, v_pages, *aslot):
            """One speculative verify step: current + K draft tokens through
            a single forward, longest-prefix acceptance via the replayed
            sampling chain (smp.verify_accept). Per-request output is
            IDENTICAL to sequential decoding for greedy AND seeded-sampled
            slots: every window row samples with the same
            fold_in(slot_key, position) key the one-token path would use at
            that position, so a draft is accepted exactly when the chain
            draws it. LoRA slots verify against their adapter's logits
            (gathered einsum inside decode_verify)."""
            toks = jnp.concatenate([tokens[:, None], drafts], axis=1)
            out = llama.decode_verify(
                mcfg, params, toks, positions, block_tables, room,
                k_pages, v_pages, page_size=page_size,
                adapter_slots=aslot[0] if aslot else None,
            )
            state = smp.SamplingState(
                temperature, top_p, top_k, presence, frequency,
                min_p, bias_ids, bias_vals,
            )
            (emitted, n_acc, tokens_new, pos_new, ctx_new,
             counts) = _spec_accept(out.logits, drafts, tokens, positions,
                                    context_lens, active, state, slot_keys,
                                    counts, room)
            return (rep((emitted, n_acc)), tokens_new, pos_new, ctx_new,
                    counts, out.k_pages, out.v_pages) + moe_tail(
                        out.moe_stats)

        def mixed_spec_fn(params, tokens, drafts, positions, context_lens,
                          active, block_tables, temperature, top_p, top_k,
                          presence, frequency, min_p, bias_ids, bias_vals,
                          slot_keys, counts, room, k_pages, v_pages, *extra):
            """ONE ragged step where every decode slot runs a speculative
            verify window AND the inflight prefill chunk rides the same
            program — spec_fn x mixed_fn (llama.mixed_verify_step routes
            both row kinds through ragged_verify_attention). The leading
            operands match spec_fn exactly (_ragged_step builds one list
            for both); the chunk operands trail and are fresh uploads each
            call, like mixed_fn's."""
            # extra layout: [adapter_slots]? + (p_tokens, p_start, p_len,
            # p_pages) + [p_adapter_slot]? — like mixed_fn
            aslots = None
            if lora_on:
                aslots, extra = extra[0], extra[1:]
            p_tokens, p_start, p_len, p_pages = extra[:4]
            p_aslot = extra[4] if lora_on else None
            toks = jnp.concatenate([tokens[:, None], drafts], axis=1)
            out = llama.mixed_verify_step(
                mcfg, params, toks, positions, block_tables, room,
                p_tokens, p_start, p_len, p_pages, k_pages, v_pages,
                page_size=page_size, adapter_slots=aslots,
                chunk_adapter_slot=p_aslot,
            )
            state = smp.SamplingState(
                temperature, top_p, top_k, presence, frequency,
                min_p, bias_ids, bias_vals,
            )
            (emitted, n_acc, tokens_new, pos_new, ctx_new,
             counts) = _spec_accept(out.logits, drafts, tokens, positions,
                                    context_lens, active, state, slot_keys,
                                    counts, room)
            # chunk_logits go back raw: the host samples the first token
            # only on the FINAL chunk (same tail as mixed_fn)
            return (rep((emitted, n_acc)), rep(out.chunk_logits),
                    tokens_new, pos_new, ctx_new, counts,
                    out.k_pages, out.v_pages) + moe_tail(out.moe_stats)

        def sample_first(*args):
            """first_token as a program of its own, for the paths that
            read a prompt's logits before they sample."""
            return rep(first_token(*args))

        def reset_count_fn(counts, slot, token):
            """Zero a slot's penalty counts and count its first token."""
            return counts.at[slot].set(0).at[slot, token].add(1)

        def import_fn(k_pages, v_pages, idx, k_new, v_new):
            # disagg KV install: in-place page scatter (pools donated)
            if v_pages.shape[-1] == 0:
                # MLA: the latent row lives once, in the K pool
                return k_pages.at[:, idx].set(k_new), v_pages
            return (
                k_pages.at[:, idx].set(k_new),
                v_pages.at[:, idx].set(v_new),
            )

        # Bind this engine's attention backend + mesh around every call
        # (traces happen inside the first call, so the kernel selection and
        # shard_map mesh are baked per-engine — not via process globals).
        backend = cfg.attention_backend
        mesh = self.mesh
        lane_blocks = self.kv_spec.lane_blocks
        # whether a hybrid model's state updates walk the live slots only
        # (the kernel) or every slot (its XLA twin): metrics.ssm
        self._ssm_live_only = False
        if mcfg.mixer_types and not mcfg.conv_state:
            with att_ops.attention_context(backend, mesh, lane_blocks):
                self._ssm_live_only = ssm_ops.update_backend(
                    self.kv_spec.ssm_shape) != "xla"

        # raw jitted fns, for warmup verification (compile-cache sizes)
        self._jit_handles = {}

        def program(name, fn, donated=(), steps=False, donated_at=()):
            """One row of the table below -> what the engine calls: `fn`
            with the parameters NAMED in `donated` (and the positions
            `donated_at`) donated, jitted unless enforce_eager, inside this
            engine's attention scope. steps=True marks a program that runs
            the model's layers: where the expert layers count, its last
            output is their counts, banked at each call."""
            if not cfg.enforce_eager:
                fn = jax.jit(fn, donate_argnums=_argnums(fn, *donated)
                             + tuple(donated_at))
                if name:
                    self._jit_handles[name] = fn

            def call(*args):
                with att_ops.attention_context(backend, mesh, lane_blocks):
                    out = fn(*args)
                if steps and counts_moe:
                    self.metrics.observe_moe(
                        out[-1], "sparse" if mcfg.is_sala else "moe")
                    out = out[:-1]
                return out

            return call

        # Donated: the KV pools + the carried decode state, which XLA then
        # updates in place. Everything else (active mask, block tables,
        # sampling params, bias arrays, slot keys, the per-call chunk
        # operands) is REUSED by the next dispatch and must never be
        # donated: a TPU deletes a donated buffer ('Array has been deleted'
        # on the next use) where the CPU only warns, so no CPU test can see
        # a wrong tuple. Hence donation is declared by parameter NAME and
        # resolved against each function's own signature (_argnums).
        kv = ("k_pages", "v_pages")
        carry = ("tokens", "positions", "context_lens", "counts") + kv
        self._prefill = program("prefill", prefill_fn, kv, True)
        self._prefill_batch = program("prefill_batch", prefill_batch_fn, kv,
                                      True)
        self._prefill_chunk = program("prefill_chunk", chunk_fn, kv, True)
        self._windows = {
            (m, lp): program(f"window_{m}_{lp}", f, carry, True)
            for (m, lp), f in window_fns.items()}
        self._mixed = {lp: program(f"mixed_{lp}", f, carry, True)
                       for lp, f in mixed_fns.items()}
        self._spec = program("spec", spec_fn, carry, True)
        self._mixed_spec = program("mixed_spec", mixed_spec_fn, carry, True)
        self._sample_first = program("sample_first", sample_first)
        # no handle: the warm-up's count of programs (`compiled_programs`,
        # which the benchmark holds constant) never had this one
        self._sample_first_batch = program(None, sample_first_batch)
        self._reset_count = program("reset_count", reset_count_fn,
                                    ("counts",))
        self._import = program("import", import_fn, kv)
        if self._state_snaps is not None:
            # the two copies between a state slot and the snapshot pool
            axis = self.kv_spec.state_slot_axis
            self._save_state_prog = program(
                "save_state", functools.partial(save_state, axis=axis),
                ("snaps",))
            self._restore_state_prog = program(
                "restore_state", functools.partial(restore_state, axis=axis),
                ("k_state", "v_state"))

        def guided_window(multi: bool, lp: bool):
            """Guided decode-window variant, built on first use
            (warmup()'s __warm_guided/__warm_guided_lp requests trigger
            all four variants before /ready). The carried grammar state
            (gmode/gdepth/gbits: the first three *extra operands, after
            the lora adapter-slot operand when there is one) is donated
            like the other carry; gactive (the next position) is
            reused."""
            if (multi, lp) not in self._guided_windows:
                self._ensure_guide_table()
                fn = make_decode_window(n_multi if multi else 1, lp,
                                        guide_tables=self._guide_dev)
                g0 = _argnums(fn, "extra")[0] + (1 if lora_on else 0)
                self._guided_windows[multi, lp] = program(
                    f"window_guided_{multi}_{lp}", fn, carry, True,
                    donated_at=(g0, g0 + 1, g0 + 2))
            return self._guided_windows[multi, lp]

        self._get_guided_window = guided_window
        # what every chunk but a prompt's last hands its mixed step: no
        # row to install, a greedy lane (the sampler's cheapest gate)
        self._first_idle = FirstRow(
            jnp.int32(0), jnp.bool_(False), jnp.zeros((2,), jnp.uint32),
            self._first_sampling(GenRequest("", [], temperature=0.0)))
        if cfg.enforce_eager:
            self._upload = lambda *xs: tuple(jnp.asarray(x) for x in xs)
        else:
            # jitted upload whose outputs share the sharding provenance of
            # other jit outputs over the engine mesh (see _decode_once).
            # optimization_barrier defeats jit's pass-through fast path for
            # identity functions; the explicit replicated out_shardings over
            # self.mesh matches what the decode windows produce.
            self._upload = jax.jit(
                lambda *xs: jax.lax.optimization_barrier(xs),
                out_shardings=rep_sharding)

    def set_kv_event_sink(self, sink) -> None:
        """Attach the cluster KV event plane: `sink(kind, [hash bytes],
        tier)` receives stored/demoted/removed block events from both the
        prefix cache and the KVBM tiers (kvbm/events.py publishes them)."""
        if self.prefix_cache is not None:
            self.prefix_cache.event_sink = sink
        if self.kvbm is not None:
            self.kvbm.events = sink

    def reset_metrics(self) -> None:
        """Fresh metrics (post-warmup, bench phase boundaries)."""
        self.metrics = EngineMetrics()
        # drop compile-time outliers from the step timeline too: bench
        # bubble baselines must reflect steady-state serving only
        self.timeline.reset()

    def compiled_program_count(self) -> int:
        """Total executables across the engine's jit caches (warmup check)."""
        return sum(f._cache_size() for f in self._jit_handles.values())

    def warmup(self) -> Dict[str, int]:
        """Precompile every program the serving loop can hit — all prefill
        buckets, every decode-window variant, the first-token sampler, and
        the disagg KV import — so /ready never flips before the engine is
        compile-complete (the XLA analogue of the reference's TRT engine
        build; with JAX_COMPILATION_CACHE_DIR set, a restart re-warms from
        the persistent cache in seconds).

        All warm traffic targets the reserved trash page 0 with inactive
        batch state, so no live KV or slot bookkeeping is disturbed."""
        if self.cfg.enforce_eager:
            return {"programs": 0, "seconds": 0}
        if self.has_work:
            raise RuntimeError("warmup() requires an idle engine")
        # every first call below compiles inside its dispatch seam: not a
        # hang, and not a sample of steady-state seam time
        self.watchdog.derive_deadline = False
        cfg = self.cfg
        t0 = time.monotonic()
        k = max(1, cfg.num_scheduler_steps)

        # Warm with REAL requests through the live code path — hand-crafted
        # jit calls can't reproduce the exact (sharding, layout, donation)
        # cache keys the serving loop produces, and a near-miss means a
        # compile on first traffic anyway.
        reqs: List[GenRequest] = []
        cap = -(-cfg.max_seq_len // cfg.page_size) * cfg.page_size
        b = cfg.page_size
        buckets = set()
        while b < cap:
            buckets.add(b)
            b *= 2
        buckets.add(cap)
        # second pass ("c"): now-cached prefixes route through the
        # chunked-suffix path, compiling its per-bucket page-table widths
        # too (the prefill role serves via prefill_only, which never
        # consults the cache — a second pass there would just re-run every
        # bucket and delay /ready)
        passes = "bc" if ((self.prefix_cache is not None
                           or self._prefix_recomputed)
                          and cfg.disaggregation_mode != "prefill") else "b"
        for tag in passes:
            for bucket in sorted(buckets):
                p = min(bucket, cfg.max_seq_len - 1)
                # distinct tokens per bucket: identical prompts would hit
                # the prefix cache and skip the full-prefill compilation
                # the first pass exists to trigger
                toks = [(bucket * 7 + j) % 97 + 1 for j in range(p)]
                reqs.append(GenRequest(f"__warm_{tag}{bucket}", toks,
                                       max_tokens=1, temperature=0.0,
                                       ignore_eos=True))
        longest = reqs[-1]
        # decode windows: max_tokens = 2k+2 runs two consecutive fused-k
        # windows (first with rebuilt state, second with carried state — the
        # two distinct steady-state signatures) and then a single-step
        # window; the logprobs twin compiles both lp variants
        reqs.append(GenRequest("__warm_win", [1, 2, 3], max_tokens=2 * k + 2,
                               temperature=0.0, ignore_eos=True))
        reqs.append(GenRequest("__warm_lp", [1, 2, 3], max_tokens=2 * k + 2,
                               temperature=0.0, ignore_eos=True, logprobs=1))
        # JSON-guided windows are reachable by ANY request
        # (response_format json_object), so /ready must cover them too —
        # both the 1-step and fused variants, with and without the
        # logprobs twin (want_lp is batch-wide, so one guided+logprobs
        # request anywhere selects the lp=True guided programs)
        reqs.append(GenRequest("__warm_guided", [1, 2, 3],
                               max_tokens=2 * k + 2, temperature=0.0,
                               ignore_eos=True, guided_json=True))
        reqs.append(GenRequest("__warm_guided_lp", [1, 2, 3],
                               max_tokens=2 * k + 2, temperature=0.0,
                               ignore_eos=True, guided_json=True,
                               logprobs=1))
        if cfg.disaggregation_mode == "prefill":
            # the prefill role serves prompts via prefill_only -> FULL
            # prefill at every bucket; routing warm traffic through
            # add_request would divert long prompts to the chunked path
            # and leave the large full-prefill programs uncompiled
            for r in reqs:
                self.prefill_only(r)
                self.release_parked(r.request_id)
        else:
            for r in reqs:
                # pools by kind and state slots serve no cached prefix, or
                # (states kept at block boundaries) none of a prompt that
                # ran as one program: the second pass is told to chunk, so
                # that a prompt whose decoders leave before it is done
                # finds its chunk program compiled
                self._warm_chunked = (
                    (self._prefix_recomputed
                     or self._state_snaps is not None)
                    and r.request_id.startswith("__warm_c"))
                self.add_request(r)
                while self.has_work:  # one at a time: fused window needs
                    self.step()       # an empty pending queue to engage
            self._warm_chunked = False
            if self._state_snaps is not None:
                # the second pass saved a state at every chunk's boundary
                # (the save program); the longest prompt once more is a hit
                # at its deepest one (the restore program)
                self.add_request(dataclasses.replace(
                    longest, request_id="__warm_s"))
                while self.has_work:
                    self.step()
            if cfg.max_prefill_batch > 1 and not (
                    self.model_cfg.layer_types or self.model_cfg.mixer_types):
                # batched-admission variants: enqueue a full same-bucket
                # burst per groupable bucket so _prefill_group's padded
                # program compiles before /ready. A bucket is groupable
                # when SOME prompt length in it passes the runtime
                # `plen <= chunk` gate — the shortest prompt that still
                # rounds to this bucket, not the bucket size itself
                # (chunk can sit mid-bucket).
                chunk = cfg.prefill_chunk_tokens
                for bucket in sorted(buckets):
                    shortest = bucket // 2 + 1 if bucket > cfg.page_size else 1
                    p = min(bucket, cfg.max_seq_len - 1)
                    if chunk > 0:
                        if shortest > chunk:
                            continue  # every prompt here takes chunked path
                        p = min(p, chunk)
                    for lane in range(cfg.max_prefill_batch):
                        toks = [(bucket * 13 + lane * 5 + j) % 89 + 1
                                for j in range(p)]
                        self.add_request(GenRequest(
                            f"__warm_g{bucket}_{lane}", toks, max_tokens=1,
                            temperature=0.0, ignore_eos=True))
                    while self.has_work:
                        self.step()
            if cfg.mixed_batch_tokens > 0:
                # unified ragged step: an anchor sequence keeps decode
                # slots live while one prompt per bucket streams in, so
                # the mixed program compiles at every page-table width
                # (plus the logprobs twin) before /ready flips. With
                # speculation on, the lp=None pass compiles the
                # mixed-verify program instead; the lp pass still compiles
                # mixed[True] (the logprobs demotion path)
                for lp in (None, 1):
                    tag = "lp" if lp else "t"
                    self.add_request(GenRequest(
                        f"__warm_m_{tag}", [5, 6, 7], max_tokens=4096,
                        temperature=0.0, ignore_eos=True, logprobs=lp))
                    self.step()  # admit the anchor (idle -> full prefill)
                    for bucket in sorted(buckets):
                        p = min(bucket, cfg.max_seq_len - 2)
                        toks = [(bucket * 11 + j) % 83 + 1 for j in range(p)]
                        self.add_request(GenRequest(
                            f"__warm_m_{tag}{bucket}", toks, max_tokens=1,
                            temperature=0.0, ignore_eos=True))
                        while self._inflight is not None or self.pending:
                            self.step()  # chunks ride mixed steps
                    self.abort_request(f"__warm_m_{tag}")
                    while self.has_work:
                        self.step()
        if cfg.disaggregation_mode == "decode":
            with self._exec_lock:
                idx = jnp.asarray([0], jnp.int32)
                one = jnp.zeros(
                    (self.kv_spec.num_layers, 1, cfg.page_size,
                     self.kv_spec.lane_width),
                    self.k_pages.dtype,
                )
                self.k_pages, self.v_pages = self._import(
                    self.k_pages, self.v_pages, idx, one,
                    one[..., :self.kv_spec.v_lane_width]
                    if self.kv_spec.index_lanes else one
                )
        # a slot retired in the carry uploads the active mask alone (warm
        # traffic runs one sequence at a time and never retires one)
        self._upload(np.zeros((cfg.max_num_seqs,), np.bool_))
        self.reset_metrics()  # don't surface warm traffic as load
        out = {
            "programs": self.compiled_program_count(),
            "seconds": round(time.monotonic() - t0, 2),
        }
        # survives reset_metrics: the jit-compile exposition
        # (dynamo_engine_warmup_seconds / _jit_programs, the bridge in
        # observability/engine_metrics.py) reads it at scrape time
        self.warmup_info = dict(out)
        # compile-complete: from here a slow seam is a hang
        self.watchdog.derive_deadline = jax.default_backend() != "cpu"
        log.info("warmup complete: %s", out)
        return out

    # ------------------------------------------------------- request intake --

    def validate_request(self, req: GenRequest) -> None:
        """Raise ValueError if the request can never be served (over-length
        prompt, a KV footprint larger than the whole pool, or an adapter
        this worker cannot serve)."""
        if req.adapter:
            if self.lora is None:
                raise ValueError(
                    "adapter requests need --lora-slots > 0 on this worker")
            if not self.lora.known(req.adapter):
                raise ValueError(f"unknown adapter {req.adapter!r}")
        if len(req.prompt_token_ids) >= self.cfg.max_seq_len:
            raise ValueError(
                f"prompt of {len(req.prompt_token_ids)} tokens exceeds "
                f"max_seq_len={self.cfg.max_seq_len}"
            )
        n_pages = max(1, -(-len(req.prompt_token_ids) // self.cfg.page_size))
        if n_pages > self.cfg.num_pages - 1:
            raise ValueError(
                f"prompt needs {n_pages} KV pages; pool only has "
                f"{self.cfg.num_pages - 1}"
            )

    # ------------------------------------------------------ per-tenant QoS --

    @staticmethod
    def _tenant_of(req: GenRequest) -> str:
        return req.tenant or "default"

    def _queue_priority(self, req: GenRequest) -> int:
        """STATIC queue-order priority: the request's own priority plus
        its tenant class's priority offset. Static by construction (no
        budget term) so the pending queue's sorted invariant cannot rot
        as balances move. Batch-class requests carry a constant penalty
        that dominates any legal priority sum — the offline lane never
        queues ahead of interactive work."""
        if self.qos is None:
            return req.priority
        c = self.qos.registry.cls(self._tenant_of(req))
        p = req.priority + c.priority
        if c.batch:
            from dynamo_tpu.qos.tenancy import BATCH_PRIORITY_PENALTY

            p += BATCH_PRIORITY_PENALTY
        return p

    def _is_batch(self, tenant: str) -> bool:
        return self.qos is not None and self.qos.registry.is_batch(tenant)

    def _class_of(self, tenant: str) -> str:
        """Flight-recorder taxonomy for preemption victims/beneficiaries."""
        return "batch" if self._is_batch(tenant) else "interactive"

    def _rank_priority(self, req: GenRequest) -> int:
        """Preemption-victim rank: queue priority plus the over-budget
        penalty — an over-budget tenant's sequences are the preferred
        victims under page/slot pressure, whatever their nominal class.
        Batch sequences add a larger penalty still: the offline lane is
        evicted before even a misbehaving interactive tenant."""
        p = self._queue_priority(req)
        if self.qos is not None:
            from dynamo_tpu.qos.tenancy import (BATCH_VICTIM_PENALTY,
                                                OVER_BUDGET_PENALTY)

            t = self._tenant_of(req)
            if self.qos.over_budget(t):
                p += OVER_BUDGET_PENALTY
            if self.qos.registry.is_batch(t):
                p += BATCH_VICTIM_PENALTY
        return p

    def _qos_slot_state(self, pend) -> tuple:
        """(held slots per tenant, demanding tenants, fair caps) over the
        running set + `pend` (a snapshot of the pending queue)."""
        held: Dict[str, int] = {}
        for s in self.seqs.values():
            t = self._tenant_of(s.req)
            held[t] = held.get(t, 0) + 1
        if self._inflight is not None:
            t = self._tenant_of(self._inflight.req)
            held[t] = held.get(t, 0) + 1
        demand = set(held) | {self._tenant_of(r) for r in pend}
        cap = {t: self.qos.slot_cap(t, self.cfg.max_num_seqs, demand)
               for t in demand}
        return held, demand, cap

    def _qos_pick_index(self) -> int:
        """Index of the next pending request to admit (caller holds
        self._lock). With QoS on, requests whose tenant is over budget or
        already holds its fair slot share are passed over while an
        admissible tenant waits behind them; when EVERY pending tenant is
        blocked the head admits anyway (work conservation — fairness must
        never idle the chip)."""
        if self.qos is None or len(self.pending) <= 1:
            return 0
        held, _, cap = self._qos_slot_state(self.pending)
        deferred: set = set()
        for i, r in enumerate(self.pending):
            t = self._tenant_of(r)
            if held.get(t, 0) >= cap[t] or self.qos.over_budget(t):
                deferred.add(t)
                continue
            if i:
                for t2 in deferred:
                    self.qos.note_defer(t2)
                self.flight.note("defer", tenants=sorted(deferred),
                                 reason="qos_share_or_budget",
                                 beneficiary_rid=r.request_id,
                                 beneficiary_tenant=t)
            return i
        return 0

    def _qos_admissible(self, req: GenRequest) -> bool:
        """Group-widening gate: may `req` take a slot right now? (caller
        holds self._lock)."""
        if self.qos is None:
            return True
        t = self._tenant_of(req)
        if self.qos.over_budget(t):
            return False
        held, _, cap = self._qos_slot_state(self.pending)
        return held.get(t, 0) < cap[t]

    def _pending_remove(self, req: GenRequest) -> None:
        """Remove `req` from the pending queue by identity (caller holds
        self._lock). Identity, not equality: the QoS pick may admit from
        the middle of the queue, and inserts between lock windows shift
        indices."""
        for i, r in enumerate(self.pending):
            if r is req:
                del self.pending[i]
                return

    def _qos_evict_batch_for_admission(self) -> List[TokenEvent]:
        """Class-wide batch eviction: interactive traffic returning to a
        trough-filled engine drains EVERY batch-held slot it needs within
        this one step — not one per step like the WFQ path, because the
        offline lane's contract is instant yield, not fair contention.
        Each victim requeues as a recompute continuation (tokens kept:
        zero lost work); the interactive admissions then land in this
        same _admit pass. Batch-vs-batch contention stays on the WFQ
        single-victim path."""
        if (self.qos is None or self._inflight is not None
                or not self.seqs):
            return []
        if not any(self._is_batch(self._tenant_of(s.req))
                   for s in self.seqs.values()):
            return []
        with self._lock:
            interactive = [r for r in self.pending
                           if not self._is_batch(self._tenant_of(r))]
        need = len(interactive) - len(self._free_slots)
        if need <= 0:
            return []
        # preemption frees pages an in-flight async window may still
        # touch — drain the pipeline before any teardown (this can also
        # finish sequences, so victims are picked after)
        events = self._materialize_pending()
        victims = sorted(
            ((slot, s) for slot, s in self.seqs.items()
             if self._is_batch(self._tenant_of(s.req))),
            key=lambda kv: (self._rank_priority(kv[1].req),
                            kv[1].req.arrival_time),
            reverse=True)
        head = interactive[0]
        for slot, seq in victims[:max(0, need)]:
            self.flight.note(
                "qos_preempt", victim_rid=seq.request_id, victim_slot=slot,
                victim_tenant=self._tenant_of(seq.req),
                victim_class="batch", reason="interactive_return",
                beneficiary_rid=head.request_id,
                beneficiary_tenant=self._tenant_of(head),
                n_out=len(seq.output_tokens))
            self._preempt_slot(slot)
        return events

    def _qos_preempt_for_admission(self) -> List[TokenEvent]:
        """WFQ slot reallocation: when every decode slot is taken and a
        well-behaved tenant queues below its fair share, preempt ONE
        sequence (worst rank, then youngest) of an over-budget tenant
        holding more than its share. At most one preemption per step
        bounds recompute thrash; the freed slot admits the waiting
        request in this same _admit pass."""
        if (self.qos is None or self._free_slots
                or self._inflight is not None or not self.seqs):
            return []
        with self._lock:
            if not self.pending:
                return []
            pend = list(self.pending)
        held, _, cap = self._qos_slot_state(pend)
        cand = next(
            (r for r in pend
             if not self.qos.over_budget(self._tenant_of(r))
             and held.get(self._tenant_of(r), 0) < cap[self._tenant_of(r)]),
            None)
        if cand is None:
            return []
        cand_t = self._tenant_of(cand)
        victims = [
            (slot, s) for slot, s in self.seqs.items()
            if self._tenant_of(s.req) != cand_t
            and self.qos.over_budget(self._tenant_of(s.req))
            and held.get(self._tenant_of(s.req), 0)
            > cap.get(self._tenant_of(s.req), 0)
        ]
        if not victims:
            return []
        # preemption frees pages an in-flight async window may still
        # touch — drain the pipeline before any teardown
        events = self._materialize_pending()
        slot, seq = max(victims, key=lambda kv: (
            self._rank_priority(kv[1].req), kv[1].req.arrival_time))
        if self.seqs.get(slot) is seq:  # materializing may have finished it
            self.flight.note(
                "qos_preempt", victim_rid=seq.request_id, victim_slot=slot,
                victim_tenant=self._tenant_of(seq.req),
                victim_class=self._class_of(self._tenant_of(seq.req)),
                reason="wfq_share",
                beneficiary_rid=cand.request_id, beneficiary_tenant=cand_t)
            self._preempt_slot(slot)
        return events

    def _qos_account(self, events: List[TokenEvent]) -> None:
        """Bank one step's decoded tokens into the tenant budgets."""
        if self.qos is None or not events:
            return
        produced: Dict[str, int] = {}
        done: List[str] = []
        for ev in events:
            if ev.token_id >= 0:
                t = self._rid_tenant.get(ev.request_id, "default")
                produced[t] = produced.get(t, 0) + 1
            if ev.finished:
                done.append(ev.request_id)
        if produced:
            demand = {self._tenant_of(s.req) for s in self.seqs.values()}
            with self._lock:
                demand.update(self._tenant_of(r) for r in self.pending)
            if self._inflight is not None:
                demand.add(self._tenant_of(self._inflight.req))
            demand.update(produced)
            self.qos.account(produced, demand)
        if done:
            with self._lock:
                for rid in done:
                    self._rid_tenant.pop(rid, None)

    def _insert_pending(self, req: GenRequest, requeue: bool = False) -> None:
        """Priority-aware queue insertion (caller holds self._lock).

        vLLM priority semantics: LOWER value admits sooner (0 default);
        with QoS on the ordering key is the request priority plus the
        tenant class's offset (_queue_priority). The queue stays ascending
        by that key with FIFO inside a level; requeued requests predate
        same-level arrivals, so they re-insert BEFORE their level's
        existing entries."""
        p = self._queue_priority(req)
        if requeue:
            idx = next((i for i, r in enumerate(self.pending)
                        if self._queue_priority(r) >= p), None)
        else:
            idx = next((i for i, r in enumerate(self.pending)
                        if self._queue_priority(r) > p), None)
        if idx is None:
            self.pending.append(req)
        else:
            self.pending.insert(idx, req)

    def add_request(self, req: GenRequest) -> None:
        """Enqueue a request (raises like validate_request).

        Priority admission (vLLM semantics: lower value = sooner, stable
        FIFO within a level). Priority also picks preemption victims under
        KV page pressure (see _preempt_for): the worst-priority youngest
        sequence is recomputed, never killed."""
        self.validate_request(req)
        with self._lock:
            self._insert_pending(req)
            self._rid_tenant[req.request_id] = self._tenant_of(req)
            self.metrics.num_requests += 1
        if req.resume_key is not None or req.prior_output_token_ids:
            # recovery seam: this request continues one that was preempted
            # or handed over from another worker — the flight ring is how a
            # post-mortem ties the continuation back to the failure
            self.flight.note(
                "resume", rid=req.request_id, tenant=self._tenant_of(req),
                n_prior=len(req.prior_output_token_ids),
                seeded=req.resume_key is not None)

    def abort_request(self, request_id: str) -> None:
        """Mark a request aborted; the scheduler thread applies it in step()."""
        with self._lock:
            self._aborted.add(request_id)

    def abort_all(self) -> List[str]:
        """Tear down every pending and running request (fatal-step recovery),
        releasing slots and KV pages. Returns the affected request ids."""
        with self._lock:
            ids = [r.request_id for r in self.pending]
            self.pending.clear()
            self._aborted.clear()
            self._rid_tenant.clear()
        self._pending_win = None  # unread tokens die with their sequences
        self._leaving.clear()
        self._release_held()
        inf, self._inflight = self._inflight, None
        if inf is not None:
            ids.append(inf.req.request_id)
            self._free_pages(inf.pages, inf.req.request_id)
            self._free_slots.append(inf.slot)
        for slot, seq in list(self.seqs.items()):
            ids.append(seq.request_id)
            self._finish_slot(slot, "abort")
        # crash/abort dump: abort_all is the fatal-step recovery path
        # (engine_service) as well as explicit teardown — either way the
        # ring tail goes to the log before the evidence scrolls away
        self.flight.dump("abort_all", rids=ids)
        cb = self.on_abort_all
        if cb is not None:
            try:
                cb(ids)
            except Exception:
                log.exception("on_abort_all hook failed")
        return ids

    def resurrect(self) -> None:
        """Rebuild device state in place after a watchdog trip: fresh KV
        pool + allocator + prefix cache, device carries invalidated,
        weights re-`device_put` through the elasticity staging path, and
        a re-warmup when the engine was warmed before.  Every live stream
        dies here (journaled ones already handed off through the drain
        plane); callers hold _exec_lock via the escalation ladder."""
        with self._exec_lock:
            t0 = time.monotonic()
            self.flight.note("resurrect_begin")
            self.abort_all()
            # a poisoned device may have corrupted any resident buffer:
            # rebuild the KV pool and everything that indexes it
            self.k_pages, self.v_pages = alloc_kv_pages(
                self.kv_spec,
                shd.replicated(self.mesh) if self.model_cfg.is_mla
                else shd.kv_sharding(self.mesh),
            )
            self.allocator = PageAllocator(self.cfg.num_pages)
            if self.win_rings is not None:
                self.win_rings = WindowRings(self.kv_spec.window_pages,
                                             self.kv_spec.ring_pages)
            if self.prefix_cache is not None:
                self._new_prefix_cache()
                if self.kvbm is not None:
                    # host-tier blocks are host RAM copies — they survive
                    # and re-onboard into the fresh pool on demand
                    self.prefix_cache.kvbm = self.kvbm
            self._invalidate_dev()
            self.token_counts = jnp.zeros(
                (self.cfg.max_num_seqs, self.model_cfg.vocab_size),
                dtype=jnp.int32)
            if not self.cfg.enforce_eager:
                (self.token_counts,) = self._upload(self.token_counts)
            # weights: round-trip through host and back onto the devices
            # via the elasticity staging idiom (leaf-for-leaf device_put
            # against the live shardings)
            self.weights.restage_live()
            if self.warmup_info is not None and not self.has_work:
                # serving sheds /v1 while unhealthy, so the engine is idle
                # here unless a direct library caller raced a submit in —
                # then first traffic pays the compile like a cold start
                self.warmup()
            self.flight.note("resurrect_done",
                             seconds=round(time.monotonic() - t0, 3))
            log.warning("engine resurrected: device state rebuilt in %.2fs",
                        time.monotonic() - t0)

    @property
    def num_active(self) -> int:
        return len(self.seqs)

    @property
    def has_work(self) -> bool:
        return (bool(self.seqs) or bool(self.pending)
                or self._inflight is not None)

    # ------------------------------------------------------------ scheduling --

    def step(self) -> List[TokenEvent]:
        """One scheduler iteration: apply aborts, admit (prefill), decode.

        step() is single-consumer: only one scheduler thread may call it.
        Producers (add_request/abort_request) synchronise via self._lock.
        Each call opens one flight-recorder draft: the segments executed
        inside fill its phases (_step_obs), decisions taken along the way
        attach as events, and the commit stamps the closing batch
        composition. A step that did no work commits nothing."""
        with self._exec_lock:
            self.flight.begin()
            self.timeline.begin_step()
            try:
                return self._step_locked()
            finally:
                if self.flight.enabled:
                    self.flight.commit(
                        active=len(self.seqs), pending=len(self.pending),
                        free_pages=self.allocator.free_pages,
                        batch=self._flight_batch())
                self.timeline.commit_step(
                    active=len(self.seqs), pending=len(self.pending))

    def _step_locked(self) -> List[TokenEvent]:
        # an armed finish-mode weight flip applies here, at the step
        # boundary, once the last old-version stream has finished — we
        # already hold _exec_lock, so no step ever mixes versions
        self.weights.maybe_flip_locked()
        events: List[TokenEvent] = []
        with self.timeline.phase("admit"):
            events.extend(self._apply_aborts())
        if self._mixed_eligible():
            # unified ragged step: the inflight chunk rides the decode
            # window — one dispatch serves both, so there is no
            # separate decode this iteration. With speculation on the
            # verify windows ride the same program (mixed_spec) unless
            # a logprobs request demotes the step to plain mixed
            # (per-position logprob extraction isn't wired through
            # verify — counted like the other spec demotions).
            spec = self.cfg.speculative_mode != "off"
            if spec and any(s.logprobs is not None
                            for s in self.seqs.values()):
                att_ops._note_fallback(
                    "spec", "logprobs",
                    "logprobs request in the batch: mixed step "
                    "runs without verify windows")
                self.flight.note("spec_demote", reason="logprobs")
                spec = False
            events.extend(self._mixed_step(spec))
        else:
            if self._inflight is not None:
                # one chunk per step: decode windows run between chunks,
                # so a long admission never monopolizes the chip
                events.extend(self._advance_chunk())
            else:
                with self.timeline.phase("admit"):
                    events.extend(self._admit())
            if self.seqs:
                if self.cfg.speculative_mode != "off":
                    events.extend(self._decode_spec())
                elif self.cfg.async_scheduling:
                    events.extend(self._decode_async())
                else:
                    events.extend(self._decode_once())
        # per-tenant QoS: bank this step's decoded tokens into the
        # weighted-fair budgets (no-op without configured tenants)
        with self.timeline.phase("bank"):
            self._qos_account(events)
        return events

    # ------------------------------------------------- flight/cost hooks --

    def _flight_batch(self) -> List[dict]:
        """Batch composition stamped on each flight record: who holds the
        decode slots (and the inflight chunk) as the step closes."""
        out: List[dict] = []
        for slot in sorted(self.seqs):
            seq = self.seqs.get(slot)
            if seq is None:
                continue
            req = seq.req
            out.append({
                "slot": slot, "rid": seq.request_id,
                "tenant": self._tenant_of(req) if req else "default",
                "adapter": (req.adapter or "") if req else "",
                "n_out": len(seq.output_tokens)})
        inf = self._inflight
        if inf is not None:
            out.append({
                "slot": inf.slot, "rid": inf.req.request_id,
                "tenant": self._tenant_of(inf.req),
                "adapter": inf.req.adapter or "",
                "chunk_done": inf.done, "prompt_len": inf.prompt_len})
        return out

    def _step_obs(self, kind: str, dur_s: float, take: int = 0,
                  shares: Optional[Dict[str, float]] = None) -> None:
        """Record one executed segment (a dispatch) in the flight draft and
        attribute its wall time + KV residency to tenants.

        `shares` (tenant -> work units) overrides the default attribution;
        without it decode slots count one unit each and the inflight chunk
        counts `take` (its tokens this segment) — the ISSUE's attribution
        rule. Holdings (KV bytes on device) always come from the live
        holder set, so byte-seconds track actual residency."""
        pb = self._page_nbytes
        sb = self.kv_spec.bytes_per_slot()  # a hybrid model's state slot
        holdings: Dict[str, float] = {}
        computed: Dict[str, float] = {}
        for seq in list(self.seqs.values()):
            t = self._tenant_of(seq.req) if seq.req is not None else "default"
            computed[t] = computed.get(t, 0.0) + 1.0
            holdings[t] = holdings.get(t, 0.0) + len(seq.pages) * pb + sb
        inf = self._inflight
        if inf is not None:
            t = self._tenant_of(inf.req)
            if take > 0:
                computed[t] = computed.get(t, 0.0) + float(take)
            holdings[t] = holdings.get(t, 0.0) + len(inf.pages) * pb + sb
        for rid, parked in list(self._parked.items()):
            t = self._rid_tenant.get(rid, "default")
            holdings[t] = holdings.get(t, 0.0) + len(parked[0]) * pb
        self.cost.account(dur_s, shares if shares is not None else computed,
                          holdings)
        if self.flight.enabled:
            self.flight.phase(kind, dur_s, **({"take": take} if take else {}))
            self._flight_note_fallback_delta()

    def _flight_note_fallback_delta(self) -> None:
        """Surface pallas/spec demotions that fired since the last segment
        as flight events (the module-level counters in ops/attention are
        the source of truth; the ring only needs the per-step delta)."""
        try:
            cur = att_ops.pallas_fallback_counts()
        except Exception:
            return
        prev = self._flight_fallback_prev
        for key, n in cur.items():
            d = n - prev.get(key, 0)
            if d > 0:
                op, reason = key
                self.flight.note("pallas_fallback" if op != "spec"
                                 else "spec_demote",
                                 op=op, reason=reason, n=d)
        self._flight_fallback_prev = dict(cur)

    def _apply_aborts(self) -> List[TokenEvent]:
        with self._lock:
            aborted, self._aborted = self._aborted, set()
        if not aborted:
            return []
        # finishing slots frees pages an in-flight async window still
        # touches and invalidates device state the rebuild needs current
        # mirrors for — drain the pipeline before any teardown. (Checked
        # AFTER the snapshot: an abort landing after it is simply next
        # step's work, where the drain re-runs.)
        events = self._materialize_pending()
        with self._lock:
            kept = collections.deque()
            for r in self.pending:
                if r.request_id in aborted:
                    events.append(TokenEvent(r.request_id, -1, 0, True, "abort"))
                    self.flight.note("abort", rid=r.request_id,
                                     tenant=self._tenant_of(r), where="queued")
                else:
                    kept.append(r)
            self.pending = kept
        inf = self._inflight
        if inf is not None and inf.req.request_id in aborted:
            self._free_pages(inf.pages, inf.req.request_id)
            self._free_slots.append(inf.slot)
            self._inflight = None
            events.append(TokenEvent(inf.req.request_id, -1, 0, True, "abort"))
            self.flight.note("abort", rid=inf.req.request_id,
                             tenant=self._tenant_of(inf.req), where="chunk",
                             slot=inf.slot)
        for slot, seq in list(self.seqs.items()):
            if seq.request_id in aborted:
                events.append(
                    TokenEvent(seq.request_id, -1, len(seq.output_tokens), True,
                               "abort", phase=_wait_phase(seq))
                )
                self._finish_slot(slot, "abort")
        return events

    def _adapter_slot(self, req: GenRequest) -> int:
        """Resolve a request's adapter name to its device slot, lazily
        loading it (LRU-evicting an idle resident if needed). 0 = base."""
        if self.lora is None or not req.adapter:
            return 0
        return self.lora.acquire_slot(req.adapter)

    def _kv_namespace(self, adapter: Optional[str]) -> str:
        """KV hash namespace for a request: the active weight version
        composed with the LoRA adapter, exactly how adapters alone used to
        namespace. The base version contributes nothing, so a never-rolled
        engine hashes byte-identically to the pre-elasticity code."""
        ver = self.weights.namespace
        a = adapter or ""
        if not ver:
            return a
        return f"{ver}#{a}"

    def _admit(self) -> List[TokenEvent]:
        events: List[TokenEvent] = []
        if self.weights.admission_held:
            # finish-mode flip armed: hold new admissions in the pending
            # queue so they land on the NEW version; in-flight streams
            # keep decoding on the old one until the flip applies
            return events
        # per-tenant QoS: interactive arrivals drain the batch class first
        # (every slot they need, this step), then slots full + a well-
        # behaved tenant below its share -> preempt ONE over-share
        # over-budget sequence
        events.extend(self._qos_evict_batch_for_admission())
        events.extend(self._qos_preempt_for_admission())
        chunk = self.cfg.prefill_chunk_tokens
        if self.kv_spec.state_layers and not self._free_slots:
            self._count_admit_blocked()
        while self._free_slots:
            with self._lock:
                if not self.pending:
                    break
                # QoS-aware pick: pass over tenants that are over budget
                # or at their fair slot share while others wait (plain
                # head-of-queue without configured tenants)
                req = self.pending[self._qos_pick_index()]
            if req.adapter:
                # resolve (and lazily device-load) the adapter BEFORE any
                # allocation: from here to installation nothing else can
                # evict the slot (group widening only admits adapters that
                # are already resident, so no further loads intervene)
                try:
                    self._adapter_slot(req)
                except NoFreeAdapterSlot:
                    self.flight.note("defer", rid=req.request_id,
                                     tenant=self._tenant_of(req),
                                     reason="no_adapter_slot",
                                     adapter=req.adapter)
                    break  # all slots serve live sequences; finishes free one
                except KeyError:
                    # unregistered between submit and admission
                    with self._lock:
                        self._pending_remove(req)
                    events.append(
                        TokenEvent(req.request_id, -1, 0, True, "abort"))
                    self.flight.note("abort", rid=req.request_id,
                                     tenant=self._tenant_of(req),
                                     reason="unknown_adapter",
                                     adapter=req.adapter)
                    continue
            # prefix lookup BEFORE the page gate: only the suffix needs
            # fresh pages, and gating on the full prompt would let the
            # eviction pressure valve evict this very request's cached
            # prefix to satisfy an allocation it never makes
            cached_pages, n_cached, state = [], 0, None
            cache, ns = self.prefix_cache, self._kv_namespace(req.adapter)
            if cache is None:
                pass
            elif self._state_snaps is not None:
                # a hybrid model whose states are kept at block boundaries:
                # the hit ends at the deepest block that carries one
                cached_pages, n_cached, state = cache.lookup_state(
                    req.prompt_token_ids, namespace=ns)
            elif not self._prefix_recomputed:
                cached_pages, n_cached = cache.lookup(
                    req.prompt_token_ids, namespace=ns)
            if (cache is not None and not cached_pages
                    and (self._prefix_recomputed
                         or self._state_snaps is not None)
                    and cache.has_prefix(req.prompt_token_ids,
                                         namespace=ns)):
                # pages found and nothing served: the prompt is recomputed
                # and the hit counted. Pools by kind: a hit at block b is
                # exact only if the sliding layers' rows of [b - window, b)
                # are still held, and a ring is its sequence's own. A
                # hybrid model: the hit needs the state at b, and a state
                # larger than a block's KV is kept nowhere; one that is
                # kept was at none of these blocks (their prompt ran as one
                # program, or the snapshots went with evicted pages)
                self.metrics.prefix_hits_inexact += 1
            n_pages = max(
                1, -(-len(req.prompt_token_ids) // self.cfg.page_size)
            )
            if not self._ensure_pages(n_pages - len(cached_pages)):
                if cached_pages:
                    self.allocator.free(cached_pages)  # drop our refs
                self.flight.note("defer", rid=req.request_id,
                                 tenant=self._tenant_of(req),
                                 reason="no_pages",
                                 need_pages=n_pages - len(cached_pages),
                                 free_pages=self.allocator.free_pages)
                if self.kv_spec.state_layers:
                    self.metrics.admit_blocked["pages"] += 1
                break  # wait for running sequences to release pages
            with self._lock:
                self._pending_remove(req)
            if chunk > 0 and (n_cached > 0
                              or len(req.prompt_token_ids) > chunk
                              or self._warm_chunked
                              or (self.cfg.mixed_batch_tokens > 0
                                  and bool(self.seqs))):
                # long (or partially cached) prompt: prefill the remainder
                # in chunks across subsequent step()s instead of stalling
                # every active stream (FIFO holds: later admissions wait).
                # Mixed mode routes EVERY prompt here while decode slots
                # are live — the chunks then ride the unified ragged step
                # instead of preempting it (an idle engine still takes the
                # faster full/batched prefill below). Starting one reserves
                # a slot and pages on the host and changes no decode
                # membership: the in-flight program keeps running
                self._start_inflight(req, cached_pages, n_cached, state)
                break
            # installing a slot invalidates the device carry: drain the
            # in-flight async program before membership changes
            events.extend(self._materialize_pending())
            group = self._widen_group(req, chunk)
            if len(group) > 1:
                got = self._prefill_group(group)
                if got is None:
                    # pages vanished between ensure and alloc (shouldn't
                    # happen with cumulative accounting, but never spin):
                    # end this admission pass; decode will free pages
                    break
                events.extend(got)
                continue
            t0 = time.monotonic()
            try:
                got = self._run_prefill(req, events)
            except OutOfPages:
                self.metrics.kv_oom += 1
                events.append(
                    TokenEvent(req.request_id, -1, 0, True, "kv_oom")
                )
                self.flight.note("kv_oom", rid=req.request_id,
                                 tenant=self._tenant_of(req), where="prefill")
                continue
            if got is not None:
                events.append(self._finalize_admission(req, *got, t0))
        return events

    def _count_admit_blocked(self) -> None:
        """A hybrid model's admission pass with no free state slot: if a
        request waits, its head lacked a slot, and pages too where the free
        ones do not cover its prompt (metrics.admit_blocked)."""
        with self._lock:
            if not self.pending:
                return
            req = self.pending[self._qos_pick_index()]
        self.metrics.admit_blocked["state_slots"] += 1
        if not self.allocator.can_alloc(max(
                1, -(-len(req.prompt_token_ids) // self.cfg.page_size))):
            self.metrics.admit_blocked["pages"] += 1

    def _widen_group(self, req: GenRequest, chunk: int) -> List[GenRequest]:
        """Pull further pending same-bucket full-prefill requests into one
        batched admission (up to max_prefill_batch, bounded by free slots
        and page supply). Requests on the chunked/cached path stay queued
        for the normal loop. A model whose layers are of more than one kind
        admits one at a time (llama.prefill_batch turns no ring), and so
        does a hybrid model (a lane would need a state slot of its own)."""
        if self.model_cfg.layer_types or self.model_cfg.mixer_types:
            return [req]
        cfg = self.cfg
        group = [req]
        if cfg.max_prefill_batch <= 1:
            return group
        bucket = _next_bucket(len(req.prompt_token_ids), cfg.page_size,
                              cfg.max_seq_len)
        # pages the whole group will allocate — INCLUDING the lead request's
        # (its earlier ensure was against the pool alone; the group's
        # members must be ensured cumulatively or the later alloc can fail
        # after every ensure passed)
        pending_need = max(
            1, -(-len(req.prompt_token_ids) // cfg.page_size))
        while (len(group) < cfg.max_prefill_batch
               and len(self._free_slots) > len(group)):
            with self._lock:
                if not self.pending:
                    break
                nxt = self.pending[0]
                if not self._qos_admissible(nxt):
                    break  # over-budget/over-share tenant: own pass later
            plen = len(nxt.prompt_token_ids)
            if chunk > 0 and plen > chunk:
                break  # chunked path
            if _next_bucket(plen, cfg.page_size, cfg.max_seq_len) != bucket:
                break  # different compile bucket
            if nxt.adapter and (self.lora is None
                                or self.lora.slot_of(nxt.adapter) is None):
                # non-resident adapter: admit it on its own pass so the
                # lazy device load (which may LRU-evict a slot an earlier
                # group member just resolved) never runs mid-group
                break
            if (self.prefix_cache is not None
                    and self.prefix_cache.has_prefix(
                        nxt.prompt_token_ids, namespace=self._kv_namespace(nxt.adapter))):
                break  # cached prefix -> chunked path (normal loop)
            n_pg = max(1, -(-plen // cfg.page_size))
            if not self._ensure_pages(pending_need + n_pg):
                break
            pending_need += n_pg
            with self._lock:
                self._pending_remove(nxt)
            group.append(nxt)
        return group

    def _prefill_group(self, reqs: List[GenRequest]
                       ) -> Optional[List[TokenEvent]]:
        """One batched prefill dispatch for same-bucket admissions: the
        per-dispatch host round trip (the dominant short-prompt TTFT cost
        on networked TPU backends) is paid once for the whole burst.
        Lanes are padded to max_prefill_batch with dummy all-trash rows so
        each bucket compiles exactly one batched variant."""
        cfg = self.cfg
        t0 = time.monotonic()
        bucket = _next_bucket(len(reqs[0].prompt_token_ids), cfg.page_size,
                              cfg.max_seq_len)
        npad = cfg.max_prefill_batch
        w = bucket // cfg.page_size
        tokens = np.zeros((npad, bucket), np.int32)
        seq_lens = np.ones((npad,), np.int32)
        pages_arr = np.zeros((npad, w), np.int32)
        page_lists: List[List[int]] = []
        try:
            for i, r in enumerate(reqs):
                plen = len(r.prompt_token_ids)
                pages = self.allocator.alloc(
                    max(1, -(-plen // cfg.page_size)))
                page_lists.append(pages)
                tokens[i, :plen] = r.prompt_token_ids
                seq_lens[i] = plen
                pages_arr[i, :len(pages)] = pages
        except OutOfPages:
            # give everything back and requeue: a later _admit pass retries
            # (smaller group or singles) once decode frees pages
            for pl in page_lists:
                self.allocator.free(pl)
            with self._lock:
                # priority-aware requeue: an add_request may have landed a
                # sooner-priority request at the head in between, and a
                # blind appendleft would break the queue's sorted invariant
                for r in reversed(reqs):
                    self._insert_pending(r, requeue=True)
            return None

        lx = ()
        if self.lora is not None:
            # every lane's adapter is resident by construction (_admit
            # resolved the lead, _widen_group only pulls resident ones) —
            # these acquires are LRU bumps, never loads
            aslots = np.zeros((npad,), np.int32)
            for i, r in enumerate(reqs):
                aslots[i] = self._adapter_slot(r)
            lx = (jnp.asarray(aslots),)
        with self.timeline.phase("dispatch", kind="prompt") as ph:
            logits, self.k_pages, self.v_pages = self._prefill_batch(
                self.params, jnp.asarray(tokens), jnp.asarray(seq_lens),
                self.k_pages, self.v_pages, jnp.asarray(pages_arr), *lx,
            )
            ph.done_when(logits, rows=len(reqs))
        if faults.check("engine.device_nan") is not None:
            # chaos drill: poison ONE lane (the lead request) — the
            # sentinel must abort exactly that stream while co-batched
            # lanes admit byte-identically to a fault-free run
            logits = logits.at[0].set(jnp.nan)
        finite = None
        if self.integrity != "off":
            # per-lane scalar vector, read back with the sampled tokens'
            # existing device_wait — no extra sync
            finite = jnp.isfinite(
                logits.reshape(logits.shape[0], -1)).all(axis=1)
        keys = np.zeros((npad, 2), np.uint32)
        temp = np.zeros((npad,), np.float32)
        top_p = np.ones((npad,), np.float32)
        top_k = np.zeros((npad,), np.int32)
        min_p = np.zeros((npad,), np.float32)
        bias_ids = np.full((npad, smp.BIAS_K), -1, np.int32)
        bias_vals = np.zeros((npad, smp.BIAS_K), np.float32)
        pen_rows = None
        for i, r in enumerate(reqs):
            keys[i] = np.asarray(self._request_key(r), np.uint32)
            temp[i], top_p[i], top_k[i] = r.temperature, r.top_p, r.top_k
            min_p[i] = r.min_p
            bias_ids[i], bias_vals[i] = _pack_logit_bias(r)
            pen = self._penalty_row(r)
            grow = self._guide_first_row(r)
            if pen is not None or grow is not None:
                if pen_rows is None:
                    pen_rows = np.zeros(
                        (npad, self.model_cfg.vocab_size), np.float32)
                if pen is not None:  # preempted continuation in the batch
                    pen_rows[i] = pen
                if grow is not None:  # JSON-guided: mask the first token
                    pen_rows[i] += grow
        raw_logits = logits
        if pen_rows is not None:
            logits = logits - jnp.asarray(pen_rows)
        with self.timeline.phase("dispatch", kind="prompt") as ph:
            toks, chosen, tids, tvals = self._sample_first_batch(
                logits, jnp.asarray(temp), jnp.asarray(top_p),
                jnp.asarray(top_k), jnp.asarray(min_p),
                jnp.asarray(bias_ids), jnp.asarray(bias_vals),
                jnp.asarray(keys), jnp.asarray(seq_lens - 1),
            )
            ph.done_when(toks, rows=len(reqs))
        with self.timeline.phase("device_wait"):
            toks_np, chosen_np = np.asarray(toks), np.asarray(chosen)
            tids_np, tvals_np = np.asarray(tids), np.asarray(tvals)
            finite_np = (np.asarray(finite) if finite is not None
                         else np.ones((npad,), np.bool_))
        if pen_rows is not None:
            # penalized lanes requesting logprobs: re-derive them from the
            # raw distribution (the sampler saw the penalized one)
            chosen_np, tids_np, tvals_np = (
                chosen_np.copy(), tids_np.copy(), tvals_np.copy())
            for i, r in enumerate(reqs):
                if r.logprobs is not None and pen_rows[i].any():
                    c, ti, tv = self._lp_from_raw(raw_logits[i],
                                                  int(toks_np[i]))
                    chosen_np[i] = c
                    tids_np[i], tvals_np[i] = ti, tv
        dt = time.monotonic() - t0
        self.metrics.prefill_time_s += dt
        self.metrics.observe_phase("prefill", dt, weight=len(reqs))
        shares: Dict[str, float] = {}
        for i, r in enumerate(reqs):
            t = self._tenant_of(r)
            shares[t] = shares.get(t, 0.0) + float(seq_lens[i])
            self._observe_dsa_prompt(0, int(seq_lens[i]),
                                     bucket // cfg.page_size)
        self._step_obs("prefill", dt, shares=shares)

        events: List[TokenEvent] = []
        for i, r in enumerate(reqs):
            if not finite_np[i]:
                # poisoned lane: this stream aborts, its pages go back,
                # the co-batched lanes below admit untouched
                self._abort_poisoned(events, r, page_lists[i],
                                     "prefill_group")
                continue
            self.metrics.prompt_tokens += int(seq_lens[i])
            events.append(self._finalize_admission(
                r, page_lists[i], int(seq_lens[i]), int(toks_np[i]), keys[i],
                (float(chosen_np[i]), tids_np[i], tvals_np[i]), t0))
        return events

    def _first_token_or_abort(self, events: List[TokenEvent],
                              req: GenRequest, pages, prompt_len: int,
                              last_logits, where: str,
                              slot: Optional[int] = None, req_key=None):
        """One prompt's first token from its last logits, for every path
        that samples a prompt on its own (full prefill, last chunk, a mixed
        step's ragged tail): (first, req_key, lp). Logits that are not
        finite (the integrity sentinel) end THIS stream and nothing else:
        its pages go back, so does a slot reserved for it, the fault is
        counted under `where`, the event that ends the stream joins
        `events`, and None is returned — the engine keeps serving."""
        try:
            # where the device is drained (a mixed step's own readback came
            # first) the sampling is an implicit program, and a prompt's
            with self.timeline.phase("device_wait", kind="prompt"):
                return self._first_token(req, last_logits, prompt_len,
                                         req_key)
        except IntegrityFault:
            self._abort_poisoned(events, req, pages, where, slot)
            return None

    def _abort_poisoned(self, events: List[TokenEvent], req: GenRequest,
                        pages, where: str, slot: Optional[int] = None):
        """End the one stream whose prompt gave logits that are not finite
        (see _first_token_or_abort; the grouped prefill checks each of its
        lanes and calls this for a poisoned one). A prompt seated at its
        final chunk's dispatch (_mixed_step) leaves as a sequence does:
        the program behind that chunk may be decoding its row."""
        seq = self.seqs.get(slot)
        if seq is not None and seq.req is req:
            self._finish_slot(slot, "integrity_fault")
        else:
            self._free_pages(pages, req.request_id)
            if slot is not None:
                self._free_slots.append(slot)
        self.watchdog.record_integrity_fault(
            "logits", [req.request_id], where=where)
        events.append(TokenEvent(req.request_id, -1, 0, True,
                                 "integrity_fault"))

    def _finalize_admission(self, req: GenRequest, pages, prompt_len: int,
                            first: int, req_key, lp, t_prefill_start: float,
                            slot: Optional[int] = None,
                            seq: Optional[SeqState] = None) -> TokenEvent:
        """What turns a finished prompt into a sequence, on every path:
        publish the prefix, install the slot (`slot` where _start_inflight
        reserved one for a chunked prompt, a free one otherwise; `seq`
        where the prompt was seated at its final chunk's dispatch and the
        program installed its row: the token is all it lacks),
        stop-check the first token, decorate logprobs. The event's `phase`
        is the per-request bridge the serving layer turns into trace
        spans: how long the request queued before `t_prefill_start`, how
        long its prompt computed (all chunks and mixed steps), and the
        stamp of this moment, from which serving/api.py times its emit."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.prompt_token_ids, pages,
                                     namespace=self._kv_namespace(req.adapter),
                                     owner=req.request_id)
        self.metrics.num_admitted += 1
        chunked = slot is not None
        if not chunked:
            slot = self._free_slots.pop()
        if seq is None:
            seq = self._install_slot(req, slot, pages, prompt_len, first,
                                     req_key)
        else:
            seq.num_tokens += 1  # it counted a token behind: _join
            self._give_first(seq, first)
            self.metrics.first_tokens_behind += 1
        finished, reason = self._check_stop(seq, first)
        ev = TokenEvent(req.request_id, first, 0, finished, reason)
        now = self.timeline.fold()
        ev.phase = {"queue_s": max(0.0, t_prefill_start - req.arrival_time),
                    "prefill_s": max(0.0, now - t_prefill_start),
                    "t_first": now}
        # token time: the sequence's waits count from here. A preempted
        # sequence's continuation keeps its account (its client kept
        # waiting), and this token ends the wait the preemption was in
        seq.token_wait = req.token_wait
        if seq.token_wait is None:
            seq.token_wait = self.timeline.token_start()
        else:
            self.timeline.token_gap(seq.token_wait, 1, req.request_id)
        if finished:
            ev.phase.update(_wait_phase(seq) or {})
        if chunked:
            # "prefill" records admission-to-first-token for BOTH paths
            # (the TTFT phase): a full prefill observed it at its dispatch,
            # per-chunk timings live in "prefill_chunk"
            self.metrics.observe_phase("prefill", ev.phase["prefill_s"])
        if req.logprobs is not None:
            self._decorate_lp(ev, seq, lp[0], lp[1], lp[2])
        if finished:
            self._finish_slot(slot, reason)
        return ev

    def _finish_inflight(self, last_logits, where: str,
                         events: List[TokenEvent]) -> None:
        """The inflight prompt's last chunk has run (alone, or in a mixed
        step's ragged tail): sample its first token from the chunk's last
        logits and install it in the slot reserved at _start_inflight."""
        inf = self._inflight
        self._inflight = None
        self.metrics.prompt_tokens += inf.prompt_len
        got = self._first_token_or_abort(
            events, inf.req, inf.pages, inf.prompt_len, last_logits, where,
            slot=inf.slot, req_key=inf.key)
        if got is not None:
            events.append(self._finalize_admission(
                inf.req, inf.pages, inf.prompt_len, *got, inf.t_start,
                slot=inf.slot))

    def _request_key(self, req: GenRequest):
        """Per-request PRNG chain root: deterministic when seeded; a
        resume_key (recovery/drain-handoff continuation) restores the
        original worker's chain root bit-exactly."""
        if req.resume_key is not None:
            return smp.key_from_snapshot(req.resume_key)
        if req.seed is not None:
            return jax.random.PRNGKey(req.seed)
        self.rng, key = jax.random.split(self.rng)
        return key

    def export_sampling_state(self, request_id: str) -> Optional[Dict]:
        """Resumable sampling-state snapshot for a LIVE sequence: the
        per-request PRNG chain root plus the output position. A drain
        handoff ships this to the frontend's journal so the continuation
        worker resumes the identical fold_in(key, position) chain —
        exact even for unseeded sampled requests, whose root key exists
        only in this process."""
        for slot, seq in list(self.seqs.items()):
            if seq.request_id == request_id:
                return {
                    "key": smp.key_snapshot(self.slot_keys[slot]),
                    "n_output": len(seq.output_tokens),
                }
        return None

    def _run_prefill(self, req: GenRequest, events: List[TokenEvent]):
        """Shared prefill: bucket, allocate pages, run the jitted prefill, and
        sample the first token. Used by both the aggregated admission path and
        the disagg prefill role.

        Returns (pages, prompt_len, first_token, req_key, lp) where lp =
        (chosen_logprob, top_ids, top_logprobs) numpy for the first token,
        or None where the integrity sentinel ended the stream (its last
        event is in `events` then)."""
        cfg = self.cfg
        t0 = time.monotonic()
        prompt = req.prompt_token_ids
        prompt_len = len(prompt)
        bucket = _next_bucket(prompt_len, cfg.page_size, cfg.max_seq_len)
        n_bucket_pages = bucket // cfg.page_size
        pages = self.allocator.alloc(max(1, -(-prompt_len // cfg.page_size)))
        self._grow_ring(req.request_id, prompt_len)
        # pad the page list to the bucket's page count with trash page 0
        pages_arr = np.zeros((n_bucket_pages,), dtype=np.int32)
        pages_arr[: len(pages)] = pages

        tokens = np.zeros((bucket,), dtype=np.int32)
        tokens[:prompt_len] = prompt

        lx = ((jnp.int32(self._adapter_slot(req)),)
              if self.lora is not None else ())
        with self.timeline.phase("dispatch", kind="prompt") as ph:
            last_logits, self.k_pages, self.v_pages = self._prefill(
                self.params,
                jnp.asarray(tokens),
                jnp.int32(prompt_len),
                self.k_pages,
                self.v_pages,
                # the slot _finalize_admission pops for this prompt next
                self._pages_operand(
                    pages_arr, req.request_id,
                    self._free_slots[-1] if self._free_slots else 0),
                *lx,
            )
            ph.done_when(last_logits, rows=1)
        if self.model_cfg.mixer_types:
            self.metrics.observe_ssm(0, 1, prompt_len,
                                     conv=self.model_cfg.conv_state)
        got = self._first_token_or_abort(events, req, pages, prompt_len,
                                         last_logits, "prefill")
        if got is None:
            return None
        first, req_key, lp = got
        dt = time.monotonic() - t0
        self.metrics.prefill_time_s += dt
        self.metrics.observe_phase("prefill", dt)
        self.metrics.prompt_tokens += prompt_len
        self._observe_dsa_prompt(0, prompt_len, n_bucket_pages)
        self._step_obs("prefill", dt,
                       shares={self._tenant_of(req): float(prompt_len)})
        return pages, prompt_len, first, req_key, lp

    # ------------------------------------------------------- JSON guide --

    def _ensure_guide_table(self) -> json_guide.VocabTable:
        """Vocab byte table for JSON-guided decoding, built once per engine
        (host numpy + device copies). HF tokenizers decompose per-token;
        otherwise ids < 256 are literal bytes (ByteTokenizer layout), sized
        to the model vocab."""
        if self._guide_table is None:
            from dynamo_tpu.engine.tokenizer import get_tokenizer

            mcfg = self.model_cfg
            eos = [mcfg.eos_token_id, *mcfg.extra_stop_token_ids]
            tok = get_tokenizer(self.cfg.model, self.cfg.model_path)
            if hasattr(tok, "tok"):
                # real tokenizer: table sized to the MODEL vocab (padded
                # embedding ids decode to nothing, never legal mid-JSON)
                table = json_guide.VocabTable.for_tokenizer(
                    tok, eos, vocab_size=mcfg.vocab_size)
            else:
                table = json_guide.VocabTable.for_byte_vocab(
                    mcfg.vocab_size, eos)
            self._guide_dev = (jnp.asarray(table.token_bytes),
                               jnp.asarray(table.token_len),
                               jnp.asarray(table.eos_mask))
            self._guide_table = table
        return self._guide_table

    def _stop_ids_for(self, req: GenRequest) -> List[int]:
        """Effective stop-token set (vLLM semantics): user stop_token_ids
        are ADDITIONAL — the model's eos ids always stop too, and
        ignore_eos exempts the MODEL eos only, never the user's explicit
        ids. The merge lives HERE (the one place that knows model_cfg), so
        the API layer passes user ids through unmodified and
        ignore_eos=true + stop_token_ids can no longer stop on model EOS.
        Guided requests keep model eos regardless of custom stops: at JSON
        completion the grammar mask only allows model eos ids, so dropping
        them would burn a completed object to finish 'length'."""
        if req.ignore_eos:
            return list(req.stop_token_ids or [])
        return list(dict.fromkeys(
            [*(req.stop_token_ids or []),
             self.model_cfg.eos_token_id,
             *self.model_cfg.extra_stop_token_ids]))

    def _guide_first_row(self, req: GenRequest):
        """First-token grammar mask as a penalty row (+1e9 on disallowed
        tokens, subtracted from the prefill logits — same hook as
        _penalty_row). Preempted continuations replay their prior output
        so the mask picks up mid-stream. Rows are cached by grammar state
        (the full-vocab host fold is ~10^8 numpy ops on a 128k vocab; the
        common fresh-request state is always START)."""
        if not req.guided_json:
            return None
        t = self._ensure_guide_table()
        state = json_guide.replay(t, req.prior_output_token_ids)
        row = self._guide_row_cache.get(state)
        if row is None:
            allow = json_guide.mask_row(t, *state)
            row = np.where(allow, 0.0, 1e9).astype(np.float32)
            if len(self._guide_row_cache) < 64:
                self._guide_row_cache[state] = row
        return row

    def _ensure_dev_guide(self) -> None:
        """(Re)build the device grammar-state arrays from the seq.guide
        host mirrors (same invalidate/rebuild protocol as _dev_state)."""
        if self._dev_guide is not None:
            return
        self._ensure_guide_table()
        b = self.cfg.max_num_seqs
        gm = np.zeros((b,), np.int32)
        gd = np.zeros((b,), np.int32)
        gb = np.zeros((b,), np.int32)
        ga = np.zeros((b,), np.bool_)
        for slot, seq in self.seqs.items():
            if seq.guide is not None:
                gm[slot], gd[slot], gb[slot] = seq.guide
                ga[slot] = True
        self._dev_guide = self._upload(gm, gd, gb, ga)

    def _penalty_row(self, req: GenRequest):
        """Presence/frequency penalty vector for a preempted continuation's
        FIRST token: 'penalties don't apply at prefill' assumes no output
        yet, which is false after preemption — the tokens in
        prior_output_token_ids are this request's own output."""
        if not req.prior_output_token_ids or not (req.presence_penalty
                                                  or req.frequency_penalty):
            return None
        row = np.zeros((self.model_cfg.vocab_size,), np.float32)
        np.add.at(row, np.asarray(req.prior_output_token_ids, np.int64), 1.0)
        return (req.presence_penalty * (row > 0).astype(np.float32)
                + req.frequency_penalty * row)

    @staticmethod
    def _lp_from_raw(raw_row, tok: int, k: int = 5):
        """Logprob fields from UNPENALIZED logits (the OpenAI contract:
        logprobs describe the model, not the sampler)."""
        logp = jax.nn.log_softmax(raw_row.astype(jnp.float32))
        tvals, tids = jax.lax.top_k(logp, k)
        return (float(logp[tok]), np.asarray(tids), np.asarray(tvals))

    @staticmethod
    def _first_sampling(req: GenRequest) -> tuple:
        """A request's sampling parameters as the first-token sampler takes
        them, one lane each (temperature, top_p, top_k, min_p, bias ids,
        bias values): sample_first's operands and FirstRow.sampling."""
        bias_ids, bias_vals = _pack_logit_bias(req)
        return (jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_p], jnp.float32),
                jnp.asarray([req.top_k], jnp.int32),
                jnp.asarray([req.min_p], jnp.float32),
                jnp.asarray(bias_ids[None]),
                jnp.asarray(bias_vals[None]))

    def _first_token(self, req: GenRequest, last_logits, prompt_len: int,
                     req_key=None):
        """Sample the first token from prefill logits (shared by the full and
        chunked prefill paths). `req_key`: the chain root where admission
        drew it already (a chunked prompt's). Returns (first, req_key, lp)."""
        if req_key is None:
            req_key = self._request_key(req)
        if faults.check("engine.device_nan") is not None:
            # chaos drill: a corrupted forward — NaN logits straight off
            # the device (integrity sentinel catches, stream aborts)
            last_logits = jnp.full_like(last_logits, jnp.nan)
        finite = None
        if self.integrity != "off":
            # one scalar, dispatched alongside the sampler and read back
            # with the first token's existing sync — no extra round trip
            finite = jnp.isfinite(last_logits).all()
        raw_logits = last_logits
        pen = self._penalty_row(req)
        if pen is not None:
            last_logits = last_logits - jnp.asarray(pen)
        grow = self._guide_first_row(req)
        if grow is not None:  # JSON-guided: mask the first token
            last_logits = last_logits - jnp.asarray(grow)
        # the prediction made FROM position prompt_len-1; decode windows fold
        # positions >= prompt_len, so the chains never collide
        tok, chosen, tids, tvals = self._sample_first(
            last_logits, *self._first_sampling(req), req_key,
            jnp.int32(prompt_len - 1),
        )
        if finite is not None and not bool(finite):
            raise IntegrityFault("logits", [req.request_id],
                                 "non-finite prefill logits")
        if pen is not None and req.logprobs is not None:
            # report logprobs from the raw distribution, not the penalized
            # one the continuation sampled from
            return int(tok), req_key, self._lp_from_raw(raw_logits, int(tok))
        return int(tok), req_key, (float(chosen), np.asarray(tids),
                                   np.asarray(tvals))

    def _seat(self, req: GenRequest, slot: int, pages, prompt_len: int,
              req_key) -> SeqState:
        """The host's part of a slot installation, for every path: the
        SeqState in `seqs` and the slot's row in every mirror the host
        owns (tables, sampling, key chain, adapter slot). The first token
        is not part of it (_give_first): a prompt whose final chunk rides
        the pipeline is seated at that chunk's dispatch and learns its
        token one program late."""
        seq = SeqState(
            req.request_id,
            slot,
            pages,
            prompt_len,
            max_tokens=req.max_tokens,
            temperature=req.temperature,
            top_p=req.top_p,
            top_k=req.top_k,
            stop_token_ids=self._stop_ids_for(req),
            logprobs=req.logprobs,
        )
        seq.prompt_ids = list(req.prompt_token_ids)
        seq.req = req
        seq.adapter_slot = self._adapter_slot(req)  # resident: a dict hit
        self.adapter_slots[slot] = seq.adapter_slot
        self.seqs[slot] = seq
        self.block_tables[slot, :] = 0
        self.block_tables[slot, : len(pages)] = pages
        if self.win_rings is not None:
            self.win_tables[slot] = self.win_rings.row(req.request_id)
        self.temperature[slot] = req.temperature
        self.top_p[slot] = req.top_p
        self.top_k[slot] = req.top_k
        self.presence[slot] = req.presence_penalty
        self.frequency[slot] = req.frequency_penalty
        self.min_p[slot] = req.min_p
        self.bias_ids[slot], self.bias_vals[slot] = _pack_logit_bias(req)
        self.slot_keys[slot] = np.asarray(req_key, dtype=np.uint32)
        return seq

    def _give_first(self, seq: SeqState, first: int) -> None:
        """A seated sequence's first output token, as the host read it."""
        seq.output_tokens.append(first)
        self.cur_tokens[seq.slot] = first
        self.metrics.output_tokens += 1
        req = seq.req
        self.flight.note("admit", rid=req.request_id, slot=seq.slot,
                         tenant=self._tenant_of(req),
                         adapter=req.adapter or "",
                         prompt_len=seq.prompt_len, pages=len(seq.pages))

    def _install_slot(self, req: GenRequest, slot: int, pages, prompt_len: int,
                      first: int, req_key) -> SeqState:
        """Slot installation with nothing in flight, for the agg-prefill
        and KV-import paths: the seat, the first token, the device-side
        penalty-count reset, and a device carry rebuilt from the mirrors."""
        seq = self._seat(req, slot, pages, prompt_len, req_key)
        self._give_first(seq, first)
        if req.guided_json:
            seq.guide = json_guide.replay(
                self._ensure_guide_table(),
                [*req.prior_output_token_ids, first])
        self.token_counts = self._reset_count(
            self.token_counts, jnp.int32(slot), jnp.int32(first)
        )
        if req.prior_output_token_ids and (req.presence_penalty
                                           or req.frequency_penalty):
            # preempted continuation: tokens emitted before preemption ride
            # in the prompt for recompute but are still OUTPUT for penalty
            # purposes — re-seed the count row on top of the reset
            row = np.zeros((self.model_cfg.vocab_size,), np.int32)
            np.add.at(row, np.asarray(req.prior_output_token_ids,
                                      np.int64), 1)
            self.token_counts = self.token_counts.at[slot].add(
                jnp.asarray(row))
        self._invalidate_dev()  # new membership -> rebuild device batch state
        return seq

    @staticmethod
    def _decorate_lp(ev: TokenEvent, seq: SeqState, chosen: float,
                     tids, tvals) -> None:
        """Attach logprob fields to an event for a logprobs-requesting seq."""
        ev.logprob = float(chosen)
        n = min(int(seq.logprobs or 0), len(tids))
        ev.top_logprobs = [(int(tids[i]), float(tvals[i])) for i in range(n)]

    def _free_pages(self, pages, request_id: str) -> None:
        """Give a sequence's pages back: its page list, with pools by
        kind its ring on the sliding layers, and the snapshot pool's
        entries its prompt reserved and did not publish."""
        self.allocator.free(pages)
        if self.win_rings is not None:
            self.win_rings.release(request_id)
        if self._state_snaps is not None:
            # what a prompt saved and never published (abort, a poisoned
            # first token); nothing where insert() has tied them to blocks
            self.prefix_cache.release_states(request_id)

    def _grow_ring(self, request_id: str, tokens: int) -> None:
        """With pools by kind: make a prompt's ring on the sliding layers
        cover its `tokens` tokens (a page at a time up to the ring's size;
        past it pages are written over)."""
        if self.win_rings is not None:
            self.win_rings.grow(
                request_id, max(1, -(-tokens // self.cfg.page_size)))

    def _pages_operand(self, pages_arr, request_id: str, slot: int):
        """A prompt's page table as its program takes it: the array, with
        pools by kind the (full table, ring) pair, and for a hybrid model
        the table and the state slot (the decode slot `slot` the prompt
        will decode in) its chunks carry their state in."""
        if self.model_cfg.mixer_types:
            return llama.SlotPages(jnp.asarray(pages_arr), jnp.int32(slot))
        if self.win_rings is None:
            return jnp.asarray(pages_arr)
        return llama.ByKind(jnp.asarray(pages_arr),
                            jnp.asarray(self.win_rings.row(request_id)))

    def _ensure_pages(self, n: int) -> bool:
        """can_alloc with prefix-cache eviction as the pressure valve."""
        if self.allocator.can_alloc(n):
            return True
        # only the pressure path is timeline-worthy: eviction walks the
        # prefix cache, the happy path above is one counter compare
        with self.timeline.phase("page_alloc"):
            if self.prefix_cache is not None:
                self.prefix_cache.evict(n - self.allocator.free_pages)
                return self.allocator.can_alloc(n)
            return False

    def _start_inflight(self, req: GenRequest, cached_pages=None,
                        n_cached: int = 0,
                        state: Optional[int] = None) -> None:
        """Reserve a decode slot and the pages of a prompt that is
        prefilled in chunks, from `n_cached` on where `cached_pages` hold
        the tokens before (a prefix hit). `state`: the snapshot pool's
        entry that holds a hybrid model's state after those tokens; it
        goes into the reserved slot before the first chunk runs."""
        cfg = self.cfg
        chunk = cfg.prefill_chunk_tokens
        prompt_len = len(req.prompt_token_ids)
        bucket = self._table_bucket(
            _next_bucket(prompt_len, cfg.page_size, cfg.max_seq_len))
        total = max(1, -(-prompt_len // cfg.page_size))
        pages = list(cached_pages or [])
        pages += self.allocator.alloc(total - len(pages))
        self._grow_ring(req.request_id, prompt_len)
        # trailing TRASH slots sized for the widest window either path
        # (classic chunk or unified ragged step) can run — see
        # KVCacheSpec.page_table_width for the boundary argument
        width = self.kv_spec.page_table_width(
            bucket, max(chunk, cfg.mixed_batch_tokens))
        pages_arr = np.zeros((width,), dtype=np.int32)
        pages_arr[: len(pages)] = pages
        slot = self._free_slots.pop()
        inf = InflightPrefill(req, pages, pages_arr, prompt_len, slot,
                              self._request_key(req),
                              aslot=self._adapter_slot(req))
        inf.done = n_cached  # cached prefix blocks skip straight to suffix
        self._inflight = inf
        if state is not None:
            self._restore_state(state, slot)
        self.flight.note("chunk_start", rid=req.request_id, slot=slot,
                         tenant=self._tenant_of(req),
                         adapter=req.adapter or "", prompt_len=prompt_len,
                         cached_tokens=n_cached)

    def _table_bucket(self, bucket: int) -> int:
        """The prompt bucket a chunked prompt's page table is sized for:
        its own, so that the chunk programs' work follows the prompt.
        A model under a learned sparse selection keeps TWO widths instead
        of one a bucket: the bucket that holds index_topk tokens for every
        prompt up to it (little to select from), and the longest for all
        others. Each width costs a chunk program and two mixed programs,
        and with the selection's sort and gathers in them (and the decode
        rows' in every mixed one) the 12 widths of a 32k context compiled
        for over 1,600 s on a v5e, past what a worker is given to become
        ready (PERF.md section 6, PR 32). The price: a prompt just past
        index_topk scores and sorts over the longest BUCKET's tokens (the
        table's trash tail, KVCacheSpec.page_table_width, is not part of
        a chunk's selection: ops/attention.dsa_chunk_attention)."""
        topk = self.model_cfg.index_topk
        cfg = self.cfg
        if self.model_cfg.layer_types or self.model_cfg.mixer_types:
            # layers of more than one kind, or a hybrid model's layers (all
            # unrolled in every program): a width every factor of four
            # below the longest, down to 2,048 tokens (32,768 | 8,192 |
            # 2,048). Each program holds a period's layers unrolled, so a
            # width costs more to compile than a one-kind model's; the
            # kernels' work follows the live KV whatever the width, and a
            # sliding layer's ring has one width anyway (the ragged
            # kernel's grid follows the live KV blocks too since PR 45)
            w = _next_bucket(cfg.max_seq_len, cfg.page_size, cfg.max_seq_len)
            while w // 4 >= max(bucket, 2048):
                w //= 4
            return w
        if not topk:
            return bucket
        small = _next_bucket(min(topk, cfg.max_seq_len), cfg.page_size,
                             cfg.max_seq_len)
        return small if bucket <= small else _next_bucket(
            cfg.max_seq_len, cfg.page_size, cfg.max_seq_len)

    def _save_state(self, inf: InflightPrefill) -> None:
        """A chunk of `inf` was just dispatched and did not end the prompt:
        behind it the slot holds the state at the boundary `inf.done`, and
        a copy goes into the snapshot pool unless that block carries one
        (PrefixCache.reserve_state). One small program in stream order
        behind the chunk's, inside the chunk's own dispatch (the stepline
        counts one program); nothing is read back."""
        if self._state_snaps is None or inf.done >= inf.prompt_len:
            return
        entry = self.prefix_cache.reserve_state(
            inf.req.request_id, inf.req.prompt_token_ids, inf.done,
            namespace=self._kv_namespace(inf.req.adapter))
        if entry is not None:
            self._state_snaps = self._save_state_prog(
                self._state_snaps, self.k_pages.state, self.v_pages.state,
                jnp.int32(entry), jnp.int32(inf.slot))

    def _restore_state(self, entry: int, slot: int) -> None:
        """The snapshot pool's `entry` into the state slot `slot`, reserved
        a moment ago (so no sequence owns it, and none that a program in
        flight still updates: such a slot waits in _held): one small
        program on the in-flight program's outputs, ahead of the prompt's
        first suffix chunk. Nothing is drained and nothing read back."""
        k_state, v_state = self._restore_state_prog(
            self.k_pages.state, self.v_pages.state, self._state_snaps,
            jnp.int32(entry), jnp.int32(slot))
        self.k_pages = self.k_pages._replace(state=k_state)
        self.v_pages = self.v_pages._replace(state=v_state)
        self.prefix_cache.states_restored += 1

    def _advance_chunk(self) -> List[TokenEvent]:
        """Run ONE chunk of the inflight prefill; on the last chunk, sample
        the first token and install the sequence into a decode slot."""
        inf = self._inflight
        assert inf is not None
        t0 = time.monotonic()
        c = self.cfg.prefill_chunk_tokens
        start = inf.done
        take = min(c, inf.prompt_len - start)
        tokens = np.zeros((c,), dtype=np.int32)
        tokens[:take] = inf.req.prompt_token_ids[start:start + take]

        lx = (jnp.int32(inf.aslot),) if self.lora is not None else ()
        with self.timeline.phase("dispatch", kind="prompt") as ph:
            last_logits, self.k_pages, self.v_pages = self._prefill_chunk(
                self.params,
                jnp.asarray(tokens),
                jnp.int32(start),
                jnp.int32(take),
                self.k_pages,
                self.v_pages,
                self._pages_operand(inf.pages_arr, inf.req.request_id,
                                    inf.slot),
                *lx,
            )
            ph.done_when(last_logits, rows=1)
            inf.done += take
            self._save_state(inf)
        dt = time.monotonic() - t0
        self.metrics.prefill_time_s += dt
        self.metrics.observe_phase("prefill_chunk", dt)
        self._observe_dsa_prompt(start, take, len(inf.pages_arr))
        if self.model_cfg.layer_types or self.model_cfg.mixer_types:
            # the same kernels' work as a mixed step's chunk, no decode row
            self.metrics.observe_mixed_attention(
                [], start, take, window=self.model_cfg.sliding_window,
                sink=SLIDING in self.model_cfg.attn_sink_kinds)
        if self.model_cfg.mixer_types:
            self.metrics.observe_ssm(0, 1, take,
                                     conv=self.model_cfg.conv_state)
        if self.model_cfg.is_sala:
            self._observe_sparse(chunk=(start, take))
        # this dispatch ran the chunk alone — its tenant owns the segment
        self._step_obs("prefill_chunk", dt, take=take,
                       shares={self._tenant_of(inf.req): float(take)})
        if inf.done < inf.prompt_len:
            return []

        # final chunk: drain any in-flight async window, then the same
        # tail as every other path
        events = self._materialize_pending()
        self._finish_inflight(last_logits, "prefill_chunk", events)
        return events

    def _mixed_eligible(self) -> bool:
        """The unified ragged step serves this iteration iff a chunked
        prefill is inflight AND decode slots are live — otherwise the
        classic paths are strictly better (full/batched prefill when
        idle, plain fused windows when nothing is admitting). Speculation
        composes: step() asks _mixed_step for the program that carries
        the draft operands as ragged verify rows. Guided decode keeps the
        classic alternation — neither mixed program carries grammar
        operands (the inflight request's OWN guide still applies: its
        first token is masked host-side by _first_token, same as the
        chunk path)."""
        return (self.cfg.mixed_batch_tokens > 0
                and self._inflight is not None
                and bool(self.seqs)
                and not any(s.guide is not None
                            for s in self.seqs.values()))

    def _mixed_step(self, spec: bool) -> List[TokenEvent]:
        """One unified ragged step: a single dispatch advances every
        decode slot AND pushes the inflight prefill forward by up to
        mixed_batch_tokens (the RPA continuous-batching shape, PAPERS.md
        arxiv 2604.15464). Decode ITL stops paying for whole prefill
        chunks between windows — the chunk tokens fill the same program's
        ragged tail, and on the final chunk the first token installs from
        the fused program's own last-row logits. A slot advances one
        token, or with `spec` runs a K+1-token verify window and emits
        1..K+1 (dispatched even when no slot drafted this step: n_acc = 0
        everywhere reduces it to plain mixed semantics, and the
        compiled-program set stays bounded and warm).

        Under async scheduling a plain mixed step rides the pipeline like
        a window (_decode_async): it is dispatched on the device outputs
        of the program in flight (a window, or the chunk before it) and
        THAT program is read afterwards, so a prompt's chunks find the
        device busy. The step itself stays in flight for the next step()
        to read, the one that carries the prompt's final chunk too: its
        program samples the first token and installs the row in the
        device carry (FirstRow), the prompt is seated on the host (_join)
        and the token is emitted when the program is read, behind the
        next one. What the REQUEST carries can keep the older order for
        that last chunk (_joins): it is read at once, the host samples
        from its logits, the slot installs and the carry is rebuilt.
        A sequence that ends in the program in flight is retired in the
        carry first (_leavers, _retire) and the step runs over the others;
        one found finished when that program is read leaves the step in
        flight (_finish_slot holds its pages and its slot back). Read at
        once, in the synchronous order: under speculation (a verify's
        n-gram drafts need the newest tokens on the host, and _decode_spec
        keeps nothing in flight for a demoted step to ride behind),
        async_scheduling off, enforce_eager, and after an exit that
        dropped the carry (kv_oom, an integrity fault); drained first: no
        headroom or no pages behind the in-flight program, every decode
        row ending in it, or a device carry that a side door (import_kv)
        invalidated."""
        inf = self._inflight
        events: List[TokenEvent] = []
        ride = self._rides
        prev = self._pending_win
        got, leaving = 0, ()
        if prev is not None and ride and self._dev_state is not None:
            leaving = self._leavers(prev)
            if self._window_steps(extra=prev.lag, skip=leaving) > 0:
                # every slot that stays has prev.lag + 1 tokens of
                # headroom: pages for the one token this step writes
                # behind prev's
                with self.timeline.phase("page_alloc"):
                    got = self._grow_pages(1, events, offset=prev.lag,
                                           allow_kill=False, skip=leaving)
        if got > 0:
            self._retire(leaving)
        else:
            # nothing in flight, or nothing may run behind it: drain the
            # pipeline, then provision as a 1-step window (a verify: K+1)
            events.extend(self._materialize_pending())
            prev = None
            ahead = self.cfg.num_speculative_tokens + 1 if spec else 1
            with self.timeline.phase("page_alloc"):
                got = self._grow_pages(ahead, events)
            if not self.seqs:
                # page pressure (or the drain's finishes) emptied the
                # batch: the chunk still has its reserved pages — advance
                # it on the classic path
                events.extend(self._advance_chunk())
                return events
        final = (inf.done + self.cfg.mixed_batch_tokens >= inf.prompt_len)
        # decided before the dispatch: the program itself installs the row
        joins = final and ride and self._joins(inf.req)
        chunk_logits = self._ragged_step(
            events, inf, self._spec_drafts(got) if spec else None,
            lag=prev.lag if prev is not None else 0, joins=joins)
        if joins:
            self._join(inf)
        if prev is not None:
            self.metrics.mixed_behind += 1
            events.extend(self._materialize_window(prev))
        if (not ride or (final and not joins) or self._dev_state is None
                or not self._batch()):
            # a final chunk that does not join is read for its logits; an
            # exit that dropped the carry freed pages the step just
            # dispatched still touches (as in _decode_async); nobody is
            # left to ride behind it
            events.extend(self._materialize_pending())
        if final and not joins:
            self._finish_inflight(chunk_logits,
                                  "mixed_spec" if spec else "mixed", events)
        return events

    def _joins(self, req: GenRequest) -> bool:
        """Whether a prompt's first token can be sampled and installed by
        its final chunk's own program (mixed_fn under FirstRow.join), by
        what the REQUEST carries: not a guided one (its first token is
        masked on the host, _guide_first_row), not a preempted
        continuation whose penalties count its earlier output
        (_penalty_row; _install_slot re-seeds its count row), and not
        while the corrupted-forward drill is armed (it poisons the logits
        the host reads). What the STEP carries is _mixed_step's part:
        speculation, async_scheduling off and enforce_eager never ride."""
        penalized = bool(req.prior_output_token_ids) and bool(
            req.presence_penalty or req.frequency_penalty)
        return not (req.guided_json or penalized
                    or faults.armed("engine.device_nan"))

    def _first_row(self, inf: InflightPrefill) -> FirstRow:
        """The final chunk's operand for a prompt that joins behind it.
        The row is installed unless the first token is the sequence's last
        by what is known ahead (max_tokens 1, max_seq_len: _check_stop's
        own terms); a stop token is found at the read."""
        stays = min(inf.req.max_tokens,
                    self.cfg.max_seq_len - inf.prompt_len) > 1
        return FirstRow(jnp.int32(inf.slot), jnp.bool_(stays), inf.key,
                        self._first_sampling(inf.req))

    def _join(self, inf: InflightPrefill) -> None:
        """The prompt whose final chunk was just dispatched joins the
        batch behind it: seated now, so that the next program's batch, its
        headroom and its pages count the newcomer (_batch, _window_steps,
        _grow_pages), one token behind like every row of a program in
        flight (its first token is that program's to give, and
        _finalize_admission's to count when it is read). What the host
        owns goes up again before the next dispatch (the mask with its
        bit, its table row, its sampling row, its adapter slot); tokens,
        positions and context lengths stay the device's, where the
        program wrote the row. A newcomer whose first token is its last
        (max_tokens 1, max_seq_len) is never activated: the program was
        told not to install it, and the next dispatch finds it among
        _leavers."""
        self._inflight = None
        seq = self._seat(inf.req, inf.slot, inf.pages, inf.prompt_len,
                         inf.key)
        seq.num_tokens -= 1
        self._pending_win = self._pending_win._replace(joiner=inf)
        self._invalidate_dev(keep_carry=True)

    def _ragged_step(self, events: List[TokenEvent],
                     inf: Optional[InflightPrefill], drafted, lag: int = 0,
                     joins: bool = False):
        """Dispatch ONE program over the decode batch, on the device carry
        as it stands (the outputs of a program still in flight `lag`
        unread steps ahead of the host, or a rebuild from the mirrors).
        `drafted` = _spec_drafts' (drafts, room, nreal) runs every slot's
        verify window: read back, accounted for and emitted here. None
        advances each slot one token beside `inf`'s next chunk (a plain
        decode without a chunk is a window: _dispatch_window): the mixed
        step is left in flight as the pending program, for its caller to
        materialize now or one program late; `inf.done` advances at
        dispatch. The verify and the mixed-verify programs share their
        leading operands, the chunk's trail. `joins`: the chunk is the
        prompt's last and its program gives the first token (FirstRow; the
        row goes into the carry unless that token is the sequence's last).
        Returns the chunk's last-row logits, on the device (None without a
        chunk)."""
        px, chunk = (), None
        if inf is not None:
            c = self.cfg.mixed_batch_tokens
            start = inf.done
            take = min(c, inf.prompt_len - start)
            chunk = (start, take)
            p_tokens = np.zeros((c,), dtype=np.int32)
            p_tokens[:take] = inf.req.prompt_token_ids[start:start + take]

        t0 = time.monotonic()
        self._ensure_dev_state()
        cur, pos, ctx_lens, active_dev = self._dev_state
        batch = self._batch()
        lx = (self._dev_adapters,) if self.lora is not None else ()
        if drafted is not None:
            drafts, room, nreal = drafted
            d_drafts, d_room = self._upload(drafts, room)
            fn = self._spec if inf is None else self._mixed_spec
            kind = "decode_spec" if inf is None else "mixed_spec"
            args = (self.params, cur, d_drafts, pos, ctx_lens, active_dev,
                    self._dev_tables, *self._dev_sampling,
                    self.token_counts, d_room)
        else:
            want_lp = any(s.logprobs is not None for s in batch.values())
            fn = self._mixed[want_lp]
            args = (self.params, cur, pos, ctx_lens, active_dev,
                    self._dev_tables, *self._dev_sampling,
                    self.token_counts)
        with self.timeline.phase(
                "dispatch",
                kind="decode" if inf is None else "prompt") as ph:
            if inf is not None:  # fresh uploads each call, never donated
                px = (jnp.asarray(p_tokens), jnp.int32(start),
                      jnp.int32(take),
                      self._pages_operand(inf.pages_arr,
                                          inf.req.request_id, inf.slot))
                if drafted is None:
                    px += (self._first_row(inf) if joins
                           else self._first_idle,)
                if self.lora is not None:
                    px += (jnp.int32(inf.aslot),)
            ys, *out = fn(*args, self.k_pages, self.v_pages, *lx, *px)
            # the tokens the host reads later anyway: never donated
            ph.done_when(ys[0], rows=len(batch) + (inf is not None))
            chunk_logits = out.pop(0) if inf is not None else None
            first = out.pop(0) if drafted is None else None
            (cur, pos, ctx_lens, self.token_counts, self.k_pages,
             self.v_pages) = out
            del args  # the donated arrays die inside this span, as in
            # _dispatch_window
            if inf is not None:
                inf.done += take
                self._save_state(inf)
        self._dev_state = (cur, pos, ctx_lens, active_dev)
        slots = list(batch)
        if drafted is None:
            # the kernels' counters read the contexts the rows were handed:
            # the host's, plus the steps of the program still unread
            self._pending_win = PendingProgram(
                1, ys, want_lp, time.monotonic() - t0, slots,
                self.timeline.dispatch_seq, chunk,
                [batch[s].num_tokens + lag for s in slots], first=first)
            return chunk_logits
        with self.timeline.phase("device_wait"):
            toks = np.asarray(ys[0]).T  # [K+1, B]
            nacc_np = np.asarray(ys[1])  # [B]
            given = nacc_np + 1
        dt = time.monotonic() - t0
        self._spec_feedback(slots, room, nreal, nacc_np)
        # the dispatch IS this iteration's decode step — it feeds the same
        # ITL histograms (that is exactly what the mixed A/B measures)
        self._account_step(kind, dt, 1, slots, chunk=chunk, given=given)
        self._emit_tokens(events, slots, toks, given=given)
        return chunk_logits

    def _headroom(self, seq: SeqState) -> int:
        """Tokens `seq` may still be given, by what the host has read:
        max_tokens, max_seq_len, the block table's last column. The first
        two are _check_stop's own terms, and the table is never narrower
        than max_seq_len (EngineConfig.max_pages_per_seq), so a sequence
        finishes exactly when its headroom is used up."""
        n_out = len(seq.output_tokens)
        return min(
            seq.max_tokens - n_out,
            self.cfg.max_seq_len - (seq.prompt_len + n_out),
            self.cfg.max_pages_per_seq * self.cfg.page_size - seq.num_tokens,
        )

    # The steps a window may fuse under the shortest headroom, where a full
    # one no longer fits. One step there left the device idle a third of
    # the time where the host is the slower side (the engine thread comes
    # back 11 ms late at the median, 27 at p90, to a step of 8: PERF.md
    # section 6, PR 55); the whole headroom as one window made an arrival
    # wait out two windows of up to 15 steps where it had waited two
    # steps. Four steps in flight cover 85-95% of that idle, and an
    # arrival waits out eight.
    SHORT_WINDOW_STEPS = 4

    def _window_steps(self, extra: int = 0, skip=()) -> int:
        """How many decode steps the next dispatch may fuse (1 = classic).

        A full window (num_scheduler_steps) requires that much headroom
        (max_tokens, max_seq_len, block-table columns) of every sequence
        it runs over; under it a window is as long as the shortest
        headroom, SHORT_WINDOW_STEPS at most. So a window never crosses a
        sequence's end (it may end ON it), and no length finish or table
        overflow can occur mid-window. It is one step while prefills wait
        for a slot or a prompt just admitted has its first chunk on the
        next mixed step (admission latency beats batching round-trips).

        `extra` = tokens already committed to an in-flight (unread) window
        under async scheduling: headroom must cover BOTH windows. `skip` =
        the slots that END in that window (_leavers): the next program runs
        without them and is priced over the others. Returns 0 when not
        even a 1-step window fits on top of the in-flight one, or nobody
        stays to run it (the caller drains the pipeline and retries
        synchronously)."""
        k = self.cfg.num_scheduler_steps
        # a prompt whose chunk rides the NEXT step (a mixed step) waits
        # like a pending one: no fused window in front of its first chunk
        small = (k <= 1 or self.pending or not self.seqs
                 or self._mixed_eligible())
        want = 1 if small else k
        if skip and len(skip) == len(self.seqs):
            return 0
        for slot, seq in self.seqs.items():
            if slot in skip:
                continue
            want = min(want, self._headroom(seq) - extra)
            if want < 1:
                return 0
        return want if want == k else min(want, self.SHORT_WINDOW_STEPS)

    def _grow_pages(self, window: int, events: List[TokenEvent],
                    offset: int = 0, allow_kill: bool = True,
                    skip=()) -> int:
        """Ensure every active sequence has KV pages for the next `window`
        tokens (positions num_tokens+offset .. +offset+window-1; `offset` =
        tokens of an in-flight async window). Falls back to a 1-token window
        if the pool can't cover the full window; sequences that can't even
        get one page finish with kv_oom — unless allow_kill is False (an
        async window is in flight over those pages), where 0 is returned so
        the caller drains the pipeline first. `skip` = the slots that end
        in that window (_leavers): they get no page."""
        cfg = self.cfg
        # never provision past the block-table width: positions beyond it
        # cannot be written (the spec path asks for K+1 ahead uniformly and
        # handles per-slot shortfall via its room mask)
        pcap = cfg.max_pages_per_seq - 1
        if window > 1:
            need_total = 0
            for slot, seq in self.seqs.items():
                if slot in skip:
                    continue
                last_page = min(
                    (seq.num_tokens + offset + window - 1) // cfg.page_size,
                    pcap)
                need_total += max(0, last_page + 1 - len(seq.pages))
            if not self._ensure_pages(need_total):
                window = 1

        for slot, seq in list(self.seqs.items()):
            if self.seqs.get(slot) is not seq or slot in skip:
                # preempted by an earlier iteration's _preempt_for: the
                # snapshot entry is dead — allocating into it would leak
                # pages into a detached SeqState forever
                continue
            last_page = min(
                (seq.num_tokens + offset + window - 1) // cfg.page_size, pcap)
            if self.win_rings is not None and self.win_rings.grow(
                    seq.request_id, last_page + 1):
                self.win_tables[slot] = self.win_rings.row(seq.request_id)
                self._invalidate_dev(tables_only=True)
            need = max(0, last_page + 1 - len(seq.pages))
            if need == 0:
                continue
            if not self._ensure_pages(need):
                if not allow_kill:
                    return 0
                # vLLM posture under page pressure: PREEMPT (recompute)
                # before killing — requeue the worst victim(s) so every
                # request eventually completes; kv_oom is the last resort
                # when even an empty batch couldn't hold this sequence
                self._preempt_for(need, protect=slot)
                if not self._ensure_pages(need):
                    # no worse-or-equal victim could free enough. If this
                    # sequence alone fits an empty pool and others are
                    # running, SELF-preempt (it is the worst remaining) —
                    # kv_oom only when the pool could never hold it
                    if (len(self.seqs) > 1
                            and len(seq.pages) + need
                            <= self.cfg.num_pages - 1):
                        self._preempt_slot(slot)
                        continue
                    self.metrics.kv_oom += 1
                    events.append(
                        TokenEvent(
                            seq.request_id, -1, len(seq.output_tokens), True,
                            "kv_oom", phase=_wait_phase(seq)
                        )
                    )
                    self.flight.note("kv_oom", rid=seq.request_id, slot=slot,
                                     tenant=self._tenant_of(seq.req),
                                     where="decode", need_pages=need)
                    self._finish_slot(slot, "kv_oom")
                    continue
            for page in self.allocator.alloc(need):
                seq.pages.append(page)
                self.block_tables[slot, len(seq.pages) - 1] = page
            self._invalidate_dev(tables_only=True)
        return window

    def _preempt_for(self, need: int, protect: int) -> None:
        """Free >= `need` pages by preempting victims (worst priority,
        then youngest arrival — vLLM's order), never the protected slot.

        Preemption is BY RECOMPUTE: the victim's pages are freed and a
        continuation request (prompt := prompt + output so far, max_tokens
        reduced) re-enters the queue AT THE FRONT of its priority level.
        Correctness across the preempt/recompute boundary:
        - sampling: per-slot key chains fold by POSITION, so a seeded
          continuation samples the identical tokens the un-preempted run
          would have (tests/test_preemption.py proves it);
        - penalties: emitted-before-preemption tokens ride in
          prior_output_token_ids and re-seed the count row at re-admission;
        - streams: the serving layer keys on request_id and counts tokens
          itself, so the continuation's events append seamlessly."""
        def rank(q):  # vLLM order: WORSE = higher priority value, younger
            # with QoS on, _rank_priority folds in the tenant class offset
            # plus the over-budget penalty, so an over-budget tenant's
            # sequences are victimized before any well-behaved tenant's
            return (self._rank_priority(q.req) if q.req else 0,
                    q.req.arrival_time if q.req else 0.0)

        protected = self.seqs.get(protect)
        floor = rank(protected) if protected is not None else (-(1 << 30),)
        while not self._ensure_pages(need):
            # never preempt a BETTER-priority sequence to feed a worse one
            # (priority inversion); the caller self-preempts instead
            victims = [(s, q) for s, q in self.seqs.items()
                       if s != protect and rank(q) >= floor]
            if not victims:
                return
            slot, _ = max(victims, key=lambda kv: rank(kv[1]))
            self._preempt_slot(slot)

    def _preempt_slot(self, slot: int) -> None:
        """Preempt ONE sequence by recompute: free its pages, requeue the
        continuation at the front of its priority level."""
        seq = self.seqs.get(slot)
        if seq is None:
            return
        old = seq.req
        cont = dataclasses.replace(
            old,
            prompt_token_ids=list(seq.prompt_ids)
            + list(seq.output_tokens),
            max_tokens=seq.max_tokens - len(seq.output_tokens),
            prior_output_token_ids=list(old.prior_output_token_ids)
            + list(seq.output_tokens),
            token_wait=seq.token_wait,
        )
        log.info(
            "preempting %s under page pressure (%d output tokens "
            "recompute; priority %d)", seq.request_id,
            len(seq.output_tokens), old.priority)
        self.flight.note("preempt", rid=seq.request_id, slot=slot,
                         tenant=self._tenant_of(old),
                         n_out=len(seq.output_tokens),
                         pages_freed=len(seq.pages))
        self._finish_slot(slot, None)
        self.metrics.num_finished -= 1  # preempted, not finished
        self.metrics.num_preempted += 1
        if self.qos is not None:
            self.qos.note_preempt(self._tenant_of(old))
        with self._lock:
            self._insert_pending(cont, requeue=True)

    def _propose_ngram(self, seq: SeqState) -> List[int]:
        """Prompt-lookup drafts: match the last `ngram_lookup` tokens of the
        sequence's history (prompt + output) against earlier history and
        propose the continuation of the most recent match; fall back to
        repeating the last token (free, and exact inside degenerate loops).
        Host-side and O(history) per call — speculative mode targets
        low-batch latency where this is noise."""
        cfg = self.cfg
        k = cfg.num_speculative_tokens
        hist = seq.prompt_ids + seq.output_tokens
        n = max(1, cfg.ngram_lookup)
        if len(hist) > n:
            pat = hist[-n:]
            for i in range(len(hist) - n - 1, -1, -1):
                if hist[i:i + n] == pat:
                    cont = hist[i + n:i + n + k]
                    if cont:
                        return (cont + [hist[-1]] * k)[:k]
                    break
        return [hist[-1] if hist else 0] * k

    def _spec_demoted(self):
        """Batch-wide speculation demotions: reasons the whole verify step
        must fall back to the classic window path, counted and one-shot
        logged through the pallas-fallback plumbing
        (dynamo_pallas_fallback_total{op="spec",reason})."""
        if any(s.guide is not None for s in self.seqs.values()):
            att_ops._note_fallback(
                "spec", "guided",
                "verify samples from unmasked logits — drafts could "
                "escape the grammar")
            return True
        if any(s.logprobs is not None for s in self.seqs.values()):
            att_ops._note_fallback(
                "spec", "logprobs",
                "per-position logprob extraction is not wired through "
                "verify")
            return True
        return False

    def _spec_drafts(self, got: int):
        """Host-side draft gate for one verify step: proposals (n-gram or
        draft-model, per the drafter knob) for every slot whose acceptance
        can be nonzero. Sampled and LoRA slots draft (acceptance replays
        the per-position sampling chain; LoRA slots draft BASE logits —
        the verify forward applies the adapter); penalized slots don't —
        their counts snapshot would go stale mid-window — and neither do
        slots whose pages/limits can't cover K+1 tokens ahead, nor slots
        the draft pool can't serve this window. Per-slot demotions are
        counted (reason-keyed, one-shot-logged) instead of silently
        drafting nothing.

        Returns (drafts [B, K], room [B], nreal [B]): `nreal` is how many
        REAL tokens the drafter proposed per slot (< K when adaptive-K
        shrank the window; the row is padded to the program's fixed K by
        repeating the last real draft — padding that happens to verify is
        still correct output, but only real drafts and real-draft
        acceptances feed the metrics/controller)."""
        cfg = self.cfg
        k = cfg.num_speculative_tokens
        k1 = k + 1
        limit = min(cfg.max_seq_len,
                    cfg.max_pages_per_seq * cfg.page_size)
        drafts = np.zeros((cfg.max_num_seqs, k), np.int32)
        room = np.zeros((cfg.max_num_seqs,), np.bool_)
        nreal = np.zeros((cfg.max_num_seqs,), np.int32)
        for slot, seq in self.seqs.items():
            if (self.presence[slot] != 0.0
                    or self.frequency[slot] != 0.0):
                att_ops._note_fallback(
                    "spec", "penalties",
                    "presence/frequency counts go stale mid-window; the "
                    "slot emits one token per verify step")
                continue
            if not (got == k1 and seq.num_tokens + k1 <= limit
                    and len(seq.pages) * cfg.page_size
                    >= seq.num_tokens + k1):
                att_ops._note_fallback(
                    "spec", "page_shortfall",
                    "pool/table/length limits can't cover K+1 tokens "
                    "ahead")
                continue
            k_s = (self._adaptive.k(slot) if self._adaptive is not None
                   else k)
            if self.draft is not None:
                prop = self.draft.propose(seq, k_s)
                if prop is None:
                    att_ops._note_fallback(
                        "spec", "draft_pool",
                        "draft KV pool can't cover the window even after "
                        "LRU shedding; the slot emits one token per "
                        "verify step")
                    continue
            else:
                prop = self._propose_ngram(seq)[:k_s]
            room[slot] = True
            nreal[slot] = len(prop)
            drafts[slot] = (prop + [prop[-1]] * k)[:k]
        return drafts, room, nreal

    def _spec_feedback(self, slots, room, nreal, nacc_np) -> None:
        """Post-verify bookkeeping of a verify step, with or without a
        chunk: drafter-labeled draft/accept accounting,
        per-slot acceptance-length observations, adaptive-K controller
        feedback, and the per-window flight record. Acceptances are
        clamped to each slot's REAL draft count — padded row positions
        that happen to verify are correct output but not drafter skill
        (bit-identical to the old accounting when adaptive-K is off,
        since nreal == K wherever room holds)."""
        drafted = accepted = 0
        for s in slots:
            if not room[s]:
                continue
            n_real = int(nreal[s])
            acc = min(int(nacc_np[s]), n_real)
            drafted += n_real
            accepted += acc
            self.metrics.observe_spec_accept(acc, drafter=self.drafter_name)
            if self._adaptive is not None:
                self._adaptive.update(s, acc, n_real)
        self.metrics.add_spec_tokens(drafted, accepted,
                                     drafter=self.drafter_name)
        if drafted:
            self.flight.note("spec_verify", drafter=self.drafter_name,
                             windows=int(room[slots].sum()),
                             drafted=drafted, accepted=accepted)

    def _decode_spec(self) -> List[TokenEvent]:
        """Speculative decode step: one verify dispatch emits 1..K+1 tokens
        per speculating sequence (vLLM/TRT-LLM's n-gram speculation
        analogue). Greedy, seeded-sampled, and LoRA-attached sequences all
        speculate — acceptance replays the per-position sampling chain and
        the verify forward applies gathered adapter deltas. Logprobs and
        JSON-guided requests demote the step to the classic window path
        (counted via _spec_demoted)."""
        if self._spec_demoted():
            return self._decode_once()
        events: List[TokenEvent] = []
        with self.timeline.phase("page_alloc"):
            got = self._grow_pages(self.cfg.num_speculative_tokens + 1,
                                   events)
        if not self.seqs:
            return events
        drafted = self._spec_drafts(got)
        if drafted[1].any():
            self._ragged_step(events, None, drafted)
        else:
            # nothing drafted (all-penalized batch, page shortfall): the
            # verify forward would cost (K+1)x a decode step to emit the
            # same one token per slot — use the plain window path instead
            # (the per-slot demotions were counted by _spec_drafts)
            events.extend(self._decode_once())
        return events

    def _decode_once(self) -> List[TokenEvent]:
        """Synchronous decode: dispatch one window and read it back."""
        events: List[TokenEvent] = []
        with self.timeline.phase("page_alloc"):
            window = self._grow_pages(self._window_steps(), events)
        if not self.seqs:
            return events
        self._dispatch_window(window)
        events.extend(self._materialize_pending())
        return events

    def _decode_async(self) -> List[TokenEvent]:
        """Pipelined decode: dispatch window k+1, THEN read program k back —
        the host sync overlaps the new window's device compute. Program k
        is the pending one: a window, or a mixed step a prompt's chunk
        left in flight (_mixed_step runs the same pipeline).

        A finish does not drain the pipeline. One the host can count
        ahead (max_tokens, max_seq_len: k holds the sequence's last
        token, _leavers): window k+1 is priced over the others, the
        leaver is retired in the device carry (_retire) before k+1 is
        dispatched, and k is read afterwards, where the leaver's last
        tokens are emitted and its pages and slot go back at once: k+1
        never knew them. One found at the read of k (a stop token), with
        k+1 in flight over the leaver's row: k+1 stays in flight,
        _finish_slot holds the leaver's pages, ring and slot back until
        k+1 has been read (_held), retires the slot in the carry for
        k+2, and k+1's rows for it are dropped by _emit_tokens'
        membership check. An admission does not drain it either: where k
        carried a prompt's final chunk, the newcomer is in `seqs` since
        k's dispatch (_join) and k+1 decodes its row; k's read gives its
        first token. What still reads k+1 at once: an exit that
        drops the carry (an integrity fault), and the last sequence
        leaving; abort and preemption drain BEFORE they tear down
        (_apply_aborts, the QoS paths), kv_oom needs nothing in flight."""
        events: List[TokenEvent] = []
        if self._pending_win is not None and self._dev_state is None:
            # a side-door membership change (disagg import_kv) invalidated
            # the device carry since dispatch: materialize before rebuilding
            events.extend(self._materialize_pending())
        prev = self._pending_win
        lag = prev.lag if prev is not None else 0
        leaving = self._leavers(prev)
        window = self._window_steps(extra=lag, skip=leaving)
        if window > 0:
            with self.timeline.phase("page_alloc"):
                window = self._grow_pages(window, events, offset=lag,
                                          allow_kill=prev is None,
                                          skip=leaving)
        if not self.seqs:
            events.extend(self._materialize_pending())
            return events
        if window <= 0:
            # not enough headroom/pages to run ahead of the in-flight
            # window, or every sequence ends in it: drain it and fall back
            # to a synchronous step
            events.extend(self._materialize_pending())
            if self.seqs:
                events.extend(self._decode_once())
            return events
        self._retire(leaving)
        self._dispatch_window(window)
        if prev is not None:
            events.extend(self._materialize_window(prev))
            if self._dev_state is None or not self.seqs:
                # an exit dropped the carry and freed pages the NEW
                # in-flight window still touches: drain it now so next
                # step's admissions can't reuse them mid-flight. Or nobody
                # is left whose tokens it holds
                events.extend(self._materialize_pending())
        return events

    @property
    def _rides(self) -> bool:
        """Whether a step rides the async pipeline at all: dispatched on
        the carry of the program in flight, read one program late."""
        cfg = self.cfg
        return (cfg.async_scheduling and cfg.speculative_mode == "off"
                and not cfg.enforce_eager)

    def _batch(self) -> Dict[int, SeqState]:
        """The sequences the NEXT program runs over: `seqs` less the slots
        already retired in the carry, whose last tokens are still unread."""
        if not self._leaving:
            return self.seqs
        return {slot: seq for slot, seq in self.seqs.items()
                if slot not in self._leaving}

    @property
    def _carry_outlives_a_finish(self) -> bool:
        """Whether a slot can be retired in the device carry as it stands:
        the step rides the pipeline and nothing dropped the carry. A
        guided batch's grammar carry needs no edit: a window masks it with
        `gactive & active`, and the active mask is what a retirement
        uploads."""
        return self._rides and self._dev_state is not None

    def _leavers(self, prev: Optional[PendingProgram]):
        """The slots whose sequences END inside `prev`, the program in
        flight, `prev.lag` unread steps ahead of the host: their headroom
        is what it already holds, so the next program can run without them
        (a window never crosses a sequence's end: _window_steps). None with
        nothing in flight, or where the carry cannot be edited."""
        if prev is None or not self._carry_outlives_a_finish:
            return ()
        return [slot for slot, seq in self.seqs.items()
                if self._headroom(seq) <= prev.lag]

    def _retire(self, slots) -> None:
        """Take `slots` out of the device carry behind the program in
        flight: their rows in the host's mirrors are reset as a finish
        resets them and go up again before the next dispatch; their
        sequences stay in `seqs` until that program is read and
        _emit_tokens finishes them."""
        for slot in slots:
            self._reset_slot_mirrors(slot)
            self._leaving.add(slot)
        if slots:
            self._invalidate_dev(keep_carry=True)

    def _ensure_dev_state(self) -> None:
        """Rebuild invalidated device batch state from the host mirrors.

        Uploads go through the jitted identity `_upload` so the arrays carry
        the SAME sharding provenance as decode-window outputs — a plain
        jnp.asarray (uncommitted) input would key a second compilation of
        every window variant for the rebuild-following call."""
        cfg = self.cfg
        if self._dev_state is not None and self._dev_state[3] is None:
            # a slot was retired in the carry (_invalidate_dev(keep_carry)):
            # the mask alone; the tables and sampling rows follow below
            active_mask = np.zeros((cfg.max_num_seqs,), np.bool_)
            active_mask[list(self._batch())] = True
            self._dev_state = (*self._dev_state[:3],
                               *self._upload(active_mask))
        if self._dev_state is None:
            active = set(self.seqs)
            for slot in range(cfg.max_num_seqs):
                seq = self.seqs.get(slot)
                if seq is not None:
                    self.cur_tokens[slot] = seq.output_tokens[-1]
                    self.positions[slot] = seq.num_tokens
                    self.context_lens[slot] = seq.num_tokens + 1
                else:
                    # inactive: position 0 / trash page / context 1
                    self.positions[slot] = 0
                    self.context_lens[slot] = 1
                    self.block_tables[slot, :] = 0
                    self.win_tables[slot, :] = 0
            active_mask = np.zeros((cfg.max_num_seqs,), np.bool_)
            active_mask[list(active)] = True
            self._dev_state = self._upload_mirrors(
                self.cur_tokens, self.positions, self.context_lens,
                active_mask,
            )
            self._dev_tables = None  # block_tables zeroed above for inactive
        if self._dev_tables is None:
            if self.win_rings is None:
                (self._dev_tables,) = self._upload_mirrors(self.block_tables)
            else:
                self._dev_tables = llama.ByKind(*self._upload_mirrors(
                    self.block_tables, self.win_tables))
        if self._dev_sampling is None:
            self._dev_sampling = self._upload_mirrors(
                self.temperature, self.top_p, self.top_k,
                self.presence, self.frequency, self.min_p,
                self.bias_ids, self.bias_vals, self.slot_keys,
            )
        if self.lora is not None and self._dev_adapters is None:
            (self._dev_adapters,) = self._upload_mirrors(self.adapter_slots)

    def _upload_mirrors(self, *mirrors):
        """_upload of the host's mirrors AS THEY STAND: copies go up. An
        upload may alias the array it is handed and read it when its turn
        on the device comes (the CPU backend does), and the host writes
        its mirrors again while that upload and the program behind it are
        still queued: _join seats a newcomer right after its final
        chunk's dispatch, a finish found at a read resets its rows."""
        return self._upload(*(m.copy() for m in mirrors))

    def _dispatch_window(self, window: int) -> None:
        t0 = time.monotonic()
        with self.timeline.phase("dispatch", kind="decode") as ph:
            # chaos: a wedged device program — the sleep runs INSIDE the
            # armed dispatch seam with _exec_lock held, exactly what a
            # real hang looks like to the watchdog monitor thread
            faults.sleep_point("engine.device_hang")
            self._ensure_dev_state()
            batch = self._batch()
            want_lp = any(s.logprobs is not None for s in batch.values())
            cur, pos, ctx_lens, active_dev = self._dev_state
            # lora mode: the per-slot adapter indices ride every window
            # (slot 0 keeps base sequences on the zero delta)
            lx = (self._dev_adapters,) if self.lora is not None else ()
            args = (self.params, cur, pos, ctx_lens, active_dev,
                    self._dev_tables, *self._dev_sampling, self.token_counts,
                    self.k_pages, self.v_pages, *lx)
            # a window of one step is the classic program; every other
            # length is the fused program's, its trip count the last operand
            fused = window > 1
            nx = (jnp.int32(window),) if fused else ()
            if any(s.guide is not None for s in batch.values()):
                self._ensure_dev_guide()
                fn = self._get_guided_window(fused, want_lp)
                (ys, cur, pos, ctx_lens, self.token_counts, *grammar,
                 self.k_pages, self.v_pages) = fn(*args, *self._dev_guide,
                                                  *nx)
                self._dev_guide = (*grammar, self._dev_guide[3])
            else:
                fn = self._windows[(fused, want_lp)]
                (ys, cur, pos, ctx_lens, self.token_counts, self.k_pages,
                 self.v_pages) = fn(*args, *nx)
            self._dev_state = (cur, pos, ctx_lens, active_dev)
            ph.done_when(ys[0], steps=window, rows=len(batch))
            w = self.metrics.windows
            w["programs"] += 1
            w["steps"] += window
            w["short"] += 1 < window < self.cfg.num_scheduler_steps
            # the last references to the donated arrays die INSIDE this
            # span: on a TPU releasing them takes ~0.5 ms a window, which
            # `host_share_pct` would otherwise read as the host's
            del args
        # capture membership AT DISPATCH: a slot installed later (disagg
        # import) must not consume this window's rows. The stored duration
        # is the HOST dispatch cost; the materialize side adds its own wait
        # so interleaved work (chunk prefills, scheduling) between dispatch
        # and readback isn't double-counted into decode_window.
        self._pending_win = PendingProgram(
            window, ys, want_lp, time.monotonic() - t0, list(batch),
            self.timeline.dispatch_seq)

    def _materialize_pending(self) -> List[TokenEvent]:
        if self._pending_win is None:
            return []
        return self._materialize_window(self._pending_win)

    def _materialize_window(self, pw: PendingProgram) -> List[TokenEvent]:
        """Read one dispatched program back, a fused window or a mixed
        step: account for it, emit its tokens, and give back what was
        held for it (_release_held)."""
        if self._pending_win is pw:
            self._pending_win = None
        events: List[TokenEvent] = []
        t_wait = time.monotonic()
        # the stepline's drained account needs to know WHICH program this
        # waits for: under async scheduling a newer one is in flight
        with self.timeline.phase("device_wait", upto=pw.ticket):
            # chaos: slow-but-alive readback — must NOT trip the watchdog
            # when the delay stays under the deadline
            faults.sleep_point("engine.device_slow")
            # the rows the program wrote: a fused window's results are
            # num_scheduler_steps rows whatever its trip count
            toks = np.asarray(pw.ys[0])[:pw.lag]  # [window, B]
            # chosen [window, B], top ids and values [window, B, K]
            lps = (tuple(np.asarray(y) for y in pw.ys[1:]) if pw.want_lp
                   else None)
            first = (tuple(np.asarray(x) for x in pw.first)
                     if pw.joiner is not None else None)
        dt = pw.dispatch_s + (time.monotonic() - t_wait)
        # a mixed step IS its iteration's decode step — it feeds the same
        # ITL histograms (that is exactly what the mixed A/B measures)
        self._account_step("decode" if pw.chunk is None else "mixed", dt,
                           pw.lag, pw.slots, chunk=pw.chunk,
                           contexts=pw.contexts)
        self._emit_tokens(events, pw.slots, toks, lps=lps)
        if first is not None:
            with self.timeline.phase("detok"):
                self._admit_joiner(events, pw.joiner, *first)
        self._release_held(pw.ticket)
        return events

    def _admit_joiner(self, events: List[TokenEvent], inf: InflightPrefill,
                      tok, chosen, tids, tvals, finite) -> None:
        """The first token of the prompt whose final chunk stayed in
        flight (_join), read with that program: _finish_inflight's tail,
        one program late. A stop found here (an EOS as first token) and a
        sentinel that reads false are finishes found behind the program
        dispatched since, which decodes the newcomer's row: _finish_slot
        retires the row in the carry and holds its pages, ring and slot
        back for that program."""
        self.metrics.prompt_tokens += inf.prompt_len
        if self.integrity != "off" and not bool(finite):
            self._abort_poisoned(events, inf.req, inf.pages, "mixed",
                                 inf.slot)
            return
        events.append(self._finalize_admission(
            inf.req, inf.pages, inf.prompt_len, int(tok), inf.key,
            (float(chosen), tids, tvals), inf.t_start, slot=inf.slot,
            seq=self.seqs[inf.slot]))

    def _account_step(self, kind: str, dt: float, steps: int, slots,
                      chunk=None, given=None, contexts=None) -> None:
        """Every observation one dispatch over the decode batch owes: it
        took `dt`, advanced the device `steps` decode steps over `slots`,
        carried `chunk` = (start, take) of the inflight prompt if any, and
        a verify gave slot s `given[s]` tokens. `decode_step` votes once
        per step advanced, so verifies, fused windows and mixed steps
        carry proportional votes in the shared histogram: a verify counts
        the steps it advanced its slots on average. `contexts` = what the
        kernels' counters take the slots' contexts to be: a mixed step's
        as taken at its dispatch, else the host's as they stand (a
        program is accounted for before its tokens are emitted)."""
        m = self.metrics
        m.decode_steps += steps
        m.decode_time_s += dt
        m.observe_phase("decode_window", dt)
        votes = (steps if given is None
                 else max(1, -(-int(given[slots].sum()) // len(slots))))
        m.observe_phase("decode_step", dt / votes, weight=votes)
        m.observe_occupancy(len(slots), self.cfg.max_num_seqs)
        take = 0
        if chunk is not None:
            start, take = chunk
            m.observe_phase("mixed_step", dt)
            m.observe_mixed(take, len(slots))
        hybrid = bool(self.model_cfg.mixer_types)
        kinds = bool(self.model_cfg.layer_types) or hybrid
        if hybrid:
            m.observe_ssm(len(slots), steps, take,
                          len(slots) if self._ssm_live_only
                          else self.cfg.max_num_seqs,
                          conv=self.model_cfg.conv_state)
        if self.model_cfg.is_mla or kinds:  # read by the kernels' rooflines
            if contexts is None:
                contexts = [self.seqs[s].num_tokens for s in slots
                            if s in self.seqs]
            # a hybrid model's sliding_window is 0: its full layers alone
            w = self.model_cfg.sliding_window if kinds else None
            sink = SLIDING in self.model_cfg.attn_sink_kinds
            if chunk is not None:
                m.observe_mixed_attention(contexts, start, take, window=w,
                                          sink=sink)
            elif given is None:  # a verify does not run the decode kernel
                m.observe_decode_attention(contexts, steps, window=w,
                                           sink=sink)
            if self.model_cfg.is_dsa:
                m.observe_dsa(self.model_cfg.index_topk,
                              self._dsa_selects(self.cfg.max_pages_per_seq),
                              contexts, steps, chunk)
            if self.model_cfg.is_sala:
                self._observe_sparse(contexts, steps, chunk)
        self._step_obs(kind, dt, take=take)

    def _observe_sparse(self, contexts=(), steps: int = 1,
                        chunk=None) -> None:
        """metrics.sparse for one dispatch of a minicpm_sala model. Its
        decode rows select where their table can hold a context past
        sparse_dense_len (llama._sparse_selects, from the same shapes);
        under a narrower one every row attends densely."""
        sz = sparse_blocks.sizes_of(self.model_cfg)
        if self.cfg.max_pages_per_seq * self.cfg.page_size <= sz.dense_len:
            sz = sz._replace(dense_len=1 << 62)
        self.metrics.observe_sparse(sz, contexts, steps, chunk)

    def _dsa_selects(self, table_pages: int) -> bool:
        """Whether a program over a page table of `table_pages` pages runs
        the sparse selection (models/llama._selects, from the same
        shapes)."""
        return llama._selects(self.model_cfg,
                              table_pages * self.cfg.page_size)

    def _observe_dsa_prompt(self, start: int, take: int,
                            table_pages: int) -> None:
        """A prompt's tokens prefilled outside a step over the decode
        batch: a chunk alone, or a whole bucket-sized prefill."""
        if self.model_cfg.is_dsa:
            self.metrics.observe_dsa(
                self.model_cfg.index_topk, self._dsa_selects(table_pages),
                chunk=(start, take))

    def _emit_tokens(self, events: List[TokenEvent], slots, toks,
                     given=None, lps=None) -> None:
        """Turn one readback into TokenEvents. `toks[j, slot]` is the j-th
        token the dispatch gave `slot`: a window's column, a mixed step's
        one token, and of a verify window the first `given[slot]` = n_acc
        + 1 (every row where `given` is None). `lps` = (chosen, top ids,
        top values) indexed alike, where the batch asked for logprobs."""
        rows = toks.shape[0]
        with self.timeline.phase("detok"):
            bad_slots = ()
            if self.integrity != "off":
                # host-side SDC net: the only data that crosses back per
                # step is the token array — a corrupted id outside
                # [0, vocab) poisons detok and the KV it indexes.
                # (Logit-level checks live in the prefill readback; the
                # step programs donate their carry, so this host check is
                # the no-recompile-cost equivalent.)
                oob = (toks < 0) | (toks >= self.model_cfg.vocab_size)
                if given is not None:
                    oob &= np.arange(rows)[:, None] < given[None, :]
                oob = oob.any(axis=0)
                if oob.any():
                    bad_slots = tuple(np.flatnonzero(oob))
            for slot in slots:
                seq = self.seqs.get(slot)
                if seq is None:  # finished/aborted since dispatch
                    continue
                if slot in bad_slots:
                    # corrupted readback: abort ONLY this slot's stream
                    self.watchdog.record_integrity_fault(
                        "decode_tokens", [seq.request_id], slot=slot)
                    events.append(TokenEvent(seq.request_id, -1, 0, True,
                                             "integrity_fault",
                                             phase=_wait_phase(seq)))
                    self._finish_slot(slot, "integrity_fault")
                    continue
                last, n0 = None, len(seq.output_tokens)
                for j in range(rows if given is None else int(given[slot])):
                    tok = int(toks[j, slot])
                    seq.num_tokens += 1  # the attended token is now cached
                    seq.output_tokens.append(tok)
                    self.cur_tokens[slot] = tok
                    if seq.guide is not None:
                        # host grammar mirror keeps up with the device
                        # carry, so membership-change rebuilds resume
                        # mid-stream exactly
                        seq.guide = json_guide.advance_host(
                            self._guide_table, seq.guide, tok)
                    self.metrics.output_tokens += 1
                    finished, reason = self._check_stop(seq, tok)
                    ev = TokenEvent(
                        seq.request_id, tok, len(seq.output_tokens) - 1,
                        finished, reason,
                    )
                    if lps is not None and seq.logprobs is not None:
                        self._decorate_lp(ev, seq, lps[0][j, slot],
                                          lps[1][j, slot], lps[2][j, slot])
                    events.append(ev)
                    last = ev
                    if finished:
                        # mid-chain stop: the later tokens given to this
                        # slot are discarded (their KV lives in pages
                        # _finish_slot frees, or holds back while a
                        # program in flight still writes there); the
                        # slot's stale advanced position dies with its
                        # row: retired in the carry, or rebuilt from the
                        # mirrors
                        self._finish_slot(slot, reason)
                        break
                wait = seq.token_wait
                if wait is not None and last is not None:
                    # token time: what this slot waited behind since its
                    # last emission, ended by the tokens it just received
                    self.timeline.token_gap(
                        wait, len(seq.output_tokens) - n0, seq.request_id)
                    if last.finished:
                        last.phase = _wait_phase(seq)

    def _check_stop(self, seq: SeqState, token: int):
        if token in seq.stop_token_ids:
            return True, "stop"
        if len(seq.output_tokens) >= seq.max_tokens:
            return True, "length"
        if seq.prompt_len + len(seq.output_tokens) >= self.cfg.max_seq_len:
            return True, "length"
        return False, None

    def _finish_slot(self, slot: int, reason: Optional[str]):
        """Every route out of the running batch (finish, preempt, abort).

        With a program in flight that was dispatched on this carry, a
        stop or a length finish leaves it in flight and keeps the carry
        (metrics.finishes_behind): either the slot was retired before
        that program was dispatched (_retire: nothing on the device knows
        the sequence any more, its pages and its slot go back here), or
        the finish was found one program late and that program still
        computes the slot's row, writes a token's KV through its table
        and, in a hybrid model, updates its state slot: pages, ring and
        decode slot then wait in _held under its ticket, and the slot is
        retired in the carry for the program after it. Every other exit
        drops the carry as it always did, and its caller reads the
        program in flight before anything can reuse what was freed:
        abort and preemption drain before they tear down, kv_oom happens
        only with nothing in flight, an integrity fault is drained in the
        same step (_decode_async, _mixed_step: `_dev_state is None`)."""
        seq = self.seqs.pop(slot, None)
        if seq is None:
            return
        if reason is not None:  # reason None = preempt, noted by its caller
            self.flight.note("finish", rid=seq.request_id, slot=slot,
                             tenant=(self._tenant_of(seq.req)
                                     if seq.req is not None else "default"),
                             reason=reason, n_out=len(seq.output_tokens))
        pw = self._pending_win
        retired = slot in self._leaving
        # a first token that read poisoned (no output yet: _admit_joiner)
        # is found like a stop: nothing of the carry is in doubt
        held = (not retired
                and (reason in ("stop", "length") or not seq.output_tokens)
                and pw is not None and slot in pw.slots
                and self._carry_outlives_a_finish)
        if retired:
            self._leaving.discard(slot)
        else:
            self._reset_slot_mirrors(slot)
        if held:
            self._held.append((pw.ticket, seq.pages, seq.request_id, slot))
            rings = self.win_rings
            self.metrics.held_pages_peak = max(
                self.metrics.held_pages_peak,
                sum(len(pages) + (rings.held_by(rid) if rings else 0)
                    for _, pages, rid, _ in self._held))
        else:
            self._free_pages(seq.pages, seq.request_id)
            self._free_slots.append(slot)
        # Speculation v3 teardown: the draft pool's pages for this slot and
        # the adaptive controller's window both key on the DECODE SLOT, so
        # every route out (finish / preempt / abort) must clear them before
        # the slot's next tenant drafts
        if self.draft is not None:
            self.draft.release(slot)
        if self._adaptive is not None:
            self._adaptive.reset(slot)
        self.metrics.num_finished += 1
        if retired or held:
            self.metrics.finishes_behind += 1
        if held:
            self._invalidate_dev(keep_carry=True)
        elif not retired:
            # the freed slot's device-side block-table row must stop
            # pointing at the released pages before the next decode window
            self._invalidate_dev()

    def _reset_slot_mirrors(self, slot: int) -> None:
        """The host's rows of a slot whose sequence leaves the batch."""
        self.block_tables[slot, :] = 0
        self.win_tables[slot, :] = 0
        self.context_lens[slot] = 0
        # reset the slot's sampling mirrors: the tiered sampler's fast-path
        # gates (all-greedy / no-mask / no-penalty) read the FULL [B]
        # arrays, so one finished temperature>0 request must not force the
        # sort path on every later all-greedy batch
        self.temperature[slot] = 0.0
        self.top_p[slot] = 1.0
        self.top_k[slot] = 0
        self.presence[slot] = 0.0
        self.frequency[slot] = 0.0
        self.min_p[slot] = 0.0
        self.bias_ids[slot] = -1
        self.bias_vals[slot] = 0.0
        self.adapter_slots[slot] = 0  # unpin the LoRA slot

    def _release_held(self, upto: Optional[int] = None) -> None:
        """Give back what waited for the programs up to ticket `upto`
        (None: everything, the engine is being torn down): pages to their
        allocators, decode slots (a hybrid model's state slots) to
        _free_slots."""
        keep = []
        for entry in self._held:
            ticket, pages, rid, slot = entry
            if upto is not None and ticket > upto:
                keep.append(entry)
                continue
            self._free_pages(pages, rid)
            self._free_slots.append(slot)
        self._held = keep

    # --------------------------------------------------- disaggregation API --

    def prefill_only(self, req: GenRequest):
        """Prefill-worker role: run the prompt, sample the first token, and
        PARK the sequence (no decode slot) until its KV is exported.

        Mirrors the reference's `--is-prefill-worker` / `--disaggregation-mode
        prefill` role (/root/reference/examples/deploy/vllm/disagg.yaml:37).
        Returns (first_token, n_prompt_tokens, extras) where extras carries
        the first token's logprob fields when requested. The KV stays
        resident until export_kv()/release_parked() — the NIXL-style
        hold-until-pulled contract
        (/root/reference/examples/deploy/sglang/disagg.yaml:47-52).
        """
        if req.adapter and (self.lora is None
                            or not self.lora.known(req.adapter)):
            raise ValueError(f"unknown adapter {req.adapter!r} on this "
                             f"prefill worker")
        if len(req.prompt_token_ids) >= self.cfg.max_seq_len:
            raise ValueError("prompt exceeds max_seq_len")
        n_pages = max(1, -(-len(req.prompt_token_ids) // self.cfg.page_size))
        if n_pages > self.cfg.num_pages - 1:
            raise ValueError(
                f"prompt needs {n_pages} KV pages; pool only has "
                f"{self.cfg.num_pages - 1}"
            )
        with self._exec_lock:
            got = self._run_prefill(req, [])
        if got is None:  # pages freed, fault counted: the caller's error
            raise IntegrityFault("logits", [req.request_id],
                                 "non-finite prefill logits")
        pages, prompt_len, first, _, lp = got
        with self._lock:
            stale = self._parked.pop(req.request_id, None)
            self._parked[req.request_id] = (pages, prompt_len, time.monotonic())
        if stale is not None:
            self.allocator.free(stale[0])
        extras = {}
        if req.logprobs is not None:
            n = min(int(req.logprobs), len(lp[1]))
            extras = {
                "logprob": lp[0],
                "top_logprobs": [
                    (int(lp[1][i]), float(lp[2][i])) for i in range(n)
                ],
            }
        return first, prompt_len, extras

    def export_kv(self, request_id: str):
        """Gather a parked sequence's KV pages off the cache for transfer.

        Returns (k, v, n_tokens): arrays [L, n_pages, ps, KV*D] (numpy).
        TPU-native replacement for the NIXL KV pull: a single XLA gather per
        pool (device->host once), shipped over ICI/DCN by the transfer layer.
        """
        k, v, n_tokens = self.export_kv_device(request_id)
        return np.asarray(k), np.asarray(v), n_tokens

    def export_kv_device(self, request_id: str):
        """Device-resident twin of export_kv: the gathered pages stay
        jax.Arrays, so a same-process decode engine can install them with a
        device-to-device copy (the ICI plane) — no host bounce.

        Returns (k, v, n_tokens) with k/v [L, n_pages, ps, KV*D] on device.
        """
        with self._lock:
            pages, n_tokens, _ = self._parked[request_id]
        with self._exec_lock:
            idx = jnp.asarray(pages, jnp.int32)
            k = jnp.take(self.k_pages, idx, axis=1)
            v = jnp.take(self.v_pages, idx, axis=1)
        return k, v, n_tokens

    def release_parked(self, request_id: str):
        with self._lock:
            parked = self._parked.pop(request_id, None)
        if parked:
            self.allocator.free(parked[0])

    def expire_parked(self, ttl_s: float = 120.0) -> int:
        """Free parked sequences never pulled by a decode worker (crashed peer
        or lost ack). Returns the number expired."""
        cutoff = time.monotonic() - ttl_s
        with self._lock:
            stale = [rid for rid, (_, _, ts) in self._parked.items()
                     if ts < cutoff]
        for rid in stale:
            log.warning("expiring parked KV for %s (never pulled)", rid)
            self.release_parked(rid)
        return len(stale)

    def import_kv(self, req: GenRequest, first_token: int, k, v):
        """Decode-worker role: install transferred KV + first token as a live
        sequence, then continue decoding in the normal batch loop.

        Returns (finished, reason): finished=True when the first (prefill-
        sampled) token already terminates the request, in which case nothing
        is installed."""
        cfg = self.cfg
        n_prompt = len(req.prompt_token_ids)
        n_pages = k.shape[1]
        if (k.shape[-1] != self.kv_spec.lane_width
                or str(k.dtype) != str(self.k_pages.dtype)):
            # fail the handshake loudly: a prefill/decode kv_cache_dtype
            # mismatch must not surface as an opaque XLA shape error inside
            # the jitted page scatter mid-request
            raise ValueError(
                f"transferred KV (dtype={k.dtype}, lanes={k.shape[-1]}) "
                f"does not match this decode worker's pool "
                f"(dtype={self.k_pages.dtype}, "
                f"lanes={self.kv_spec.lane_width}) — prefill and decode "
                f"roles must use the same --kv-cache-dtype (and, for int8 "
                f"KV, the same --tensor-parallel: the rows are lane-blocked "
                f"per TP shard)")
        if req.adapter and (self.lora is None
                            or not self.lora.known(req.adapter)):
            raise ValueError(f"unknown adapter {req.adapter!r} on this "
                             f"decode worker")
        stop_ids = self._stop_ids_for(req)
        if first_token in stop_ids:
            return True, "stop"
        if req.max_tokens <= 1 or n_prompt + 1 >= cfg.max_seq_len:
            return True, "length"
        with self._exec_lock:
            return self._import_kv_locked(req, first_token, k, v, n_prompt,
                                          n_pages)

    def _import_kv_locked(self, req, first_token, k, v, n_prompt, n_pages):
        if not self._free_slots:
            raise OutOfPages("no free decode slot for imported sequence")
        # resolve (and lazily load) the adapter BEFORE any allocation so a
        # NoFreeAdapterSlot/unknown-adapter failure can't leak pages/slots
        self._adapter_slot(req)
        self._ensure_pages(n_pages)  # evict cached pages under pressure
        pages = self.allocator.alloc(n_pages)
        idx = jnp.asarray(pages, jnp.int32)
        k = jnp.asarray(k).astype(self.k_pages.dtype)
        v = jnp.asarray(v).astype(self.v_pages.dtype)
        mesh_devs = set(self.mesh.devices.flat)
        if set(k.sharding.device_set) != mesh_devs:
            # cross-sub-mesh handoff (prefill and decode on different device
            # subsets of one slice): move the pages onto THIS engine's mesh
            # with the pool's own layout before the jitted scatter — XLA
            # lowers it to a device-to-device copy (ICI on TPU), and the
            # jit below requires every operand on its mesh
            pool_sharding = jax.sharding.NamedSharding(
                self.mesh, self.k_pages.sharding.spec)
            k = jax.device_put(k, pool_sharding)
            v = jax.device_put(v, pool_sharding)
        self.k_pages, self.v_pages = self._import(
            self.k_pages, self.v_pages, idx, k, v,
        )
        slot = self._free_slots.pop()
        # seeded requests continue the same per-request key chain the prefill
        # worker started, so disagg sampling == agg sampling for a given seed
        self._install_slot(req, slot, pages, n_prompt, first_token,
                           self._request_key(req))
        with self._lock:
            self._rid_tenant[req.request_id] = self._tenant_of(req)
        self.metrics.num_requests += 1
        return False, None

    # ------------------------------------------------------------ conveniences

    def generate(self, req: GenRequest) -> List[int]:
        """Blocking single-request generation (tests, CLI)."""
        self.add_request(req)
        out: List[int] = []
        while self.has_work:
            for ev in self.step():
                if ev.request_id == req.request_id and ev.token_id >= 0:
                    out.append(ev.token_id)
        return out
