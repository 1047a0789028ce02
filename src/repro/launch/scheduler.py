"""Continuous-batching serve engine: request queue + slot scheduler.

The synchronous driver (`repro.launch.serve.serve`) prefills one fixed
batch and decodes it in lockstep, so a finished sequence leaves its cache
slot idle until the whole batch drains. This module keeps every KV-cache
slot busy every decode step instead — the serving analogue of the paper's
FPGA pipeline keeping every LUT busy every cycle (DESIGN §6):

* requests enter a FIFO queue (`Engine.submit`);
* each cache row is a *slot* with lifecycle FREE -> PREFILL -> DECODE ->
  DRAIN -> FREE;
* whenever a slot frees, the scheduler pops the queue and prefills the
  request into that row with a fixed-shape `slot_prefill_step`
  (`repro.launch.steps`), then the slot joins the already-running masked
  decode batch mid-flight — no recompilation, no barrier on neighbours.

Shape discipline (DESIGN §6): the decode step is compiled exactly once
for (slots, max_len); prefill compiles once per prompt-length bucket.
`Engine.trace_counts` counts retraces so tests can assert the steady
state compiles nothing.

Host-mesh smoke usage:

    eng = Engine(cfg, params, slots=4, max_len=64)
    eng.submit(prompt_tokens, max_new=16)
    results = eng.drain()          # -> [RequestResult]
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import time
from typing import Any, Callable, Iterable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.dist import sharding as sh
from repro.launch import steps
from repro.launch.mesh import make_host_mesh
from repro.models import kvcache, transformer
from repro.obs import jaxhooks as obs_jaxhooks
from repro.obs import metrics as obs_metrics
from repro.obs import registry as obs_registry


class SlotState(enum.Enum):
    FREE = "free"          # no request; row contents are dead
    PREFILL = "prefill"    # request admitted this step, cache being built
    DECODE = "decode"      # live: emits one token per engine step
    DRAIN = "drain"        # finished; result finalised, row reclaimed at
    #                        the next admission scan


@dataclasses.dataclass
class Request:
    """One generation request. `tokens` is the unpadded prompt (plen,)."""
    tokens: np.ndarray
    max_new: int
    rid: int = -1                      # assigned by Engine.submit
    arrival: float = 0.0               # stream offset (s) for run(realtime=)
    frames: Optional[np.ndarray] = None    # (F, D) whisper encoder frames
    patches: Optional[np.ndarray] = None   # (P, D) vision patch embeddings

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.tokens).shape[0])


@dataclasses.dataclass
class RequestResult:
    rid: int
    prompt_len: int
    tokens: List[int]                  # generated ids, len == max_new
    t_submit: float
    t_admit: float = 0.0
    t_first: float = 0.0               # first token (end of prefill)
    # None = still in flight. A sentinel, NOT 0.0: with an injected clock a
    # request can legitimately finish at time 0.0, and stats() filters on
    # `is not None` — a falsy-but-real timestamp must still count.
    t_done: Optional[float] = None

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit

    @property
    def queue_wait(self) -> float:
        return self.t_admit - self.t_submit


@dataclasses.dataclass
class _Slot:
    state: SlotState = SlotState.FREE
    request: Optional[Request] = None
    result: Optional[RequestResult] = None
    key: Any = None                    # per-request PRNG (sampled decode)


def _bucket_pow2(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class Engine:
    """Step-driven continuous-batching engine over one ServeState.

    Parameters
    ----------
    slots: batch width of the decode step == number of concurrent requests.
    max_len: cache width; every request needs prompt_len + max_new <= max_len.
    bucket: None -> prefill compiles per exact prompt length; "pow2" ->
        prompts are right-padded to the next power-of-two bucket and the
        length-aware prefill masks the tail. Padded prefill is only sound
        for full-width attention caches (DESIGN §6), so "pow2" asserts
        eligibility at construction.
    paged: block-granular KV (DESIGN §13): full-width attn/MLA caches
        become shared block pools; blocks are allocated on admission (a
        request only reserves ceil(need/block_size) blocks, not a
        worst-case max_len row) and freed on drain. Insufficient blocks
        leave the queue head waiting — backpressure, never a drop.
        SSM/recurrent/windowed leaves stay contiguous (O(1)/O(window) per
        slot already), so `paged=True` is a no-op for those families
        beyond the admission bookkeeping.
    block_size/num_blocks: [paged] block granularity (max_len must divide
        evenly) and pool size. num_blocks defaults to the contiguous
        worst case + the null block, i.e. paged-by-layout but not yet
        memory-constrained; smaller pools trade admission latency for
        memory.
    prefill_batch: [paged] up to this many same-bucket queued requests
        are prefilled in ONE launch (batched multi-slot admission —
        amortises short prompts). Partial groups pad with dummy rows.
    greedy/rng/temperature: token selection, mirroring `serve()`. Sampled
        decode draws from a per-request key (fold_in by rid) so outputs do
        not depend on which slot or step a request lands in.
    """

    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_len: int = 128, mesh=None, greedy: bool = True,
                 rng=None, temperature: float = 1.0,
                 bucket: Optional[str] = None, clock: Callable = None,
                 paged: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None, prefill_batch: int = 1):
        if bucket not in (None, "pow2"):
            raise ValueError(f"unknown bucket policy {bucket!r}")
        if bucket == "pow2" and not self._bucket_eligible(cfg):
            raise ValueError(
                "bucketed (padded) prefill needs full-width attention "
                "caches: windowed/SSM/recurrent state folds padding in "
                f"sequentially ({cfg.name})")
        self.cfg = cfg
        self.params = params
        self.num_slots = slots
        self.max_len = max_len
        self.mesh = mesh or make_host_mesh()
        self.rules = sh.SERVE_RULES
        self.greedy = greedy
        self.temperature = temperature
        self.bucket = bucket
        self.clock = clock or time.perf_counter
        self._base_key = rng if rng is not None else jax.random.PRNGKey(0)

        self.paged = bool(paged)
        if prefill_batch < 1:
            raise ValueError(f"need prefill_batch >= 1, got {prefill_batch}")
        if prefill_batch > 1 and not self.paged:
            raise ValueError(
                "prefill_batch > 1 (batched multi-slot admission) requires "
                "paged=True — the contiguous engine admits one slot per "
                "launch")
        self.prefill_batch = min(int(prefill_batch), slots)
        if self.paged:
            if block_size < 1:
                raise ValueError(f"need block_size >= 1, got {block_size}")
            if max_len % block_size:
                raise ValueError(
                    f"max_len {max_len} must be a multiple of block_size "
                    f"{block_size} so a slot's logical view tiles exactly")
            self.block_size: Optional[int] = int(block_size)
            self.blocks_per_slot = max_len // block_size
            if num_blocks is None:
                num_blocks = slots * self.blocks_per_slot + 1
            if num_blocks < self.blocks_per_slot + 1:
                raise ValueError(
                    f"num_blocks {num_blocks} cannot hold one worst-case "
                    f"request ({self.blocks_per_slot} blocks + the null "
                    "block) — an empty engine would deadlock")
            self.num_blocks: Optional[int] = int(num_blocks)
            self.allocator = kvcache.BlockAllocator(self.num_blocks)
            self.block_tables = np.zeros((slots, self.blocks_per_slot),
                                         np.int32)
            self._slot_blocks: list = [[] for _ in range(slots)]
        else:
            self.block_size = self.num_blocks = None
            self.allocator = None

        # trace-time side effects: these counters move only when jax traces
        # (== compiles) a new program, so tests can assert the warm engine
        # never recompiles. Mirrored into the global obs recorder as
        # jax.trace.* counters (DESIGN §12) by the counted() wrapper.
        self.trace_counts: collections.Counter = collections.Counter()
        self.lat_hist = obs_metrics.Histogram()
        self.queue_hist = obs_metrics.Histogram()

        if self.paged:
            prefill = steps.make_paged_prefill_step(
                cfg, max_len=max_len, admit=self.prefill_batch)
            decode = steps.make_paged_decode_step(cfg)
            prefill_donate, decode_donate = (5,), (2,)
        else:
            prefill = steps.make_slot_prefill_step(cfg, max_len=max_len)
            decode = steps.make_masked_decode_step(cfg)
            prefill_donate, decode_donate = (4,), (2,)

        self._prefill = jax.jit(
            obs_jaxhooks.counted(
                prefill, self.trace_counts,
                lambda params, batch, *a: f"prefill_{batch['tokens'].shape[1]}",
                agg_key="prefill"),
            donate_argnums=prefill_donate)
        self._decode = jax.jit(
            obs_jaxhooks.counted(decode, self.trace_counts, "decode"),
            donate_argnums=decode_donate)

        with sh.use_mesh(self.mesh, self.rules):
            if self.paged:
                state = steps.paged_serve_state_zeros(
                    cfg, params, slots, max_len,
                    block_size=self.block_size, num_blocks=self.num_blocks)
            else:
                state = steps.serve_state_zeros(cfg, params, slots, max_len)
        # placed on the engine mesh like every step's output state: a
        # single-device input would key a second trace of each step
        self.state = jax.device_put(
            state, sh.named_sharding(self.mesh, self.rules, ()))
        self.slots = [_Slot() for _ in range(slots)]
        self.queue: collections.deque = collections.deque()
        self._next_tok = np.zeros((slots,), np.int32)
        self.results: dict = {}
        self._next_rid = 0
        self.step_count = 0
        self.peak_active = 0
        self.dropped = 0

    # -- scheduling ---------------------------------------------------------

    @staticmethod
    def _bucket_eligible(cfg: ArchConfig) -> bool:
        mixers = {ls.mixer for seg in transformer.arch_segments(cfg)
                  for ls in seg.layers}
        return (mixers <= {"attn", "mla"} and not cfg.sliding_window
                and not cfg.block_pattern and not cfg.patch_tokens)

    def submit(self, tokens, max_new: int, *, frames=None,
               patches=None, arrival: float = 0.0) -> int:
        """Queue one request; returns its rid. Never drops: a full engine
        only deepens the queue (slot exhaustion queues, DESIGN §6)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        req = Request(tokens=tokens, max_new=int(max_new), arrival=arrival,
                      frames=None if frames is None else np.asarray(frames),
                      patches=None if patches is None else
                      np.asarray(patches))
        if req.prompt_len < 1 or req.max_new < 1:
            raise ValueError("need prompt_len >= 1 and max_new >= 1")
        # patch tokens prepend to the decoder sequence and occupy cache
        # rows ahead of the prompt, so they count against the ring buffer.
        # Bucket-aware: the decode budget is the REAL prompt length (the
        # padded tail sits above the kv_len mask and is overwritten by
        # decode writes), so a bucketed request is rejected only when the
        # true rows don't fit — or when the padded prefill itself exceeds
        # the cache width.
        patch = self.cfg.patch_tokens or 0
        need = patch + req.prompt_len + req.max_new
        if need > self.max_len:
            raise ValueError(
                f"request needs {need} cache rows (patches + prompt + "
                f"max_new), engine max_len is {self.max_len}")
        padded = patch + self._padded_len(req.prompt_len)
        if padded > self.max_len:
            raise ValueError(
                f"prompt pads to the {self._padded_len(req.prompt_len)} "
                f"bucket ({padded} cache rows with patches), which exceeds "
                f"engine max_len {self.max_len} even though the request "
                f"itself fits ({need} rows) — raise max_len or drop "
                "bucketing")
        req.rid = self._next_rid
        self._next_rid += 1
        self.results[req.rid] = RequestResult(
            rid=req.rid, prompt_len=req.prompt_len, tokens=[],
            t_submit=self.clock())
        self.queue.append(req)
        return req.rid

    def _padded_len(self, plen: int) -> int:
        return _bucket_pow2(plen) if self.bucket == "pow2" else plen

    def _select(self, logits_last, slot: Optional[_Slot] = None) -> int:
        """Next token from (V,) logits: greedy argmax (bit-compatible with
        `serve()`) or per-request categorical sample."""
        if self.greedy:
            return int(jnp.argmax(logits_last))
        slot.key, sub = jax.random.split(slot.key)
        return int(jax.random.categorical(
            sub, logits_last / self.temperature))

    def _admit(self):
        """Reclaim DRAIN slots (freeing their blocks when paged), then pop
        the queue into FREE rows."""
        for i, sl in enumerate(self.slots):
            if sl.state is SlotState.DRAIN:
                sl.state = SlotState.FREE
                sl.request = sl.result = None
                if self.paged and self._slot_blocks[i]:
                    self.allocator.free(self._slot_blocks[i])
                    self._slot_blocks[i] = []
                    # all-null row: the slot's masked decode writes sink
                    # into block 0 until the next admission re-tables it
                    self.block_tables[i, :] = 0
        if self.paged:
            self._admit_paged()
        else:
            self._admit_contiguous()

    def _admit_contiguous(self):
        """Batch-1 prefill-into-slot + first token from the prefill
        logits, one launch per admitted request."""
        rec = obs_registry.get_recorder()
        for i, sl in enumerate(self.slots):
            if not self.queue or sl.state is not SlotState.FREE:
                continue
            req = self.queue.popleft()
            res = self.results[req.rid]
            sl.state = SlotState.PREFILL
            sl.request = req
            sl.result = res
            sl.key = jax.random.fold_in(self._base_key, req.rid)
            res.t_admit = self.clock()
            self.queue_hist.observe(res.queue_wait)
            rec.histogram("serve.engine.queue_wait_s").observe(res.queue_wait)

            plen = self._padded_len(req.prompt_len)
            toks = np.zeros((1, plen), np.int32)
            toks[0, :req.prompt_len] = req.tokens
            batch = {"tokens": jnp.asarray(toks)}
            if req.frames is not None:
                batch["frames"] = jnp.asarray(req.frames)[None]
            if req.patches is not None:
                batch["patches"] = jnp.asarray(req.patches)[None]
            with rec.span("engine.prefill", rid=req.rid, slot=i, plen=plen):
                with sh.use_mesh(self.mesh, self.rules):
                    logits, self.state = self._prefill(
                        self.params, batch,
                        jnp.asarray(req.prompt_len, jnp.int32),
                        jnp.asarray(i, jnp.int32), self.state)
                tok = self._select(logits[0, -1], sl)
            res.tokens.append(tok)
            res.t_first = self.clock()
            self._next_tok[i] = tok
            self._finish_if_done(i, sl)
            if sl.state is SlotState.PREFILL:
                sl.state = SlotState.DECODE

    def _blocks_needed(self, req: Request) -> int:
        need = (self.cfg.patch_tokens or 0) + req.prompt_len + req.max_new
        return -(-need // self.block_size)      # ceil

    def _admit_paged(self):
        """Paged admission: group up to `prefill_batch` same-bucket queue
        heads (FIFO — a different-bucket head ends the group), allocate
        each request's blocks, and prefill the group in one launch. An
        unsatisfiable allocation leaves the head queued until a drain
        frees blocks; construction guarantees an empty engine can always
        hold one worst-case request, so `drain()` terminates."""
        rec = obs_registry.get_recorder()
        while self.queue:
            free_slots = [i for i, sl in enumerate(self.slots)
                          if sl.state is SlotState.FREE]
            if not free_slots:
                break
            bucket = self._padded_len(self.queue[0].prompt_len)
            group = []                       # (req, slot, blocks)
            while (self.queue and free_slots
                   and len(group) < self.prefill_batch):
                req = self.queue[0]
                if self._padded_len(req.prompt_len) != bucket:
                    break
                blocks = self.allocator.alloc(self._blocks_needed(req))
                if blocks is None:
                    break                    # backpressure: head waits
                self.queue.popleft()
                group.append((req, free_slots.pop(0), blocks))
            if not group:
                break
            self._launch_paged_prefill(group, bucket)
            rec.gauge("serve.engine.blocks_in_use").set(self.allocator.used)

    def _launch_paged_prefill(self, group, bucket: int):
        """One batched multi-slot prefill launch. Dummy pad rows come
        FIRST and alias the first real request's slot with an all-null
        table row: their contiguous-state write is fully overwritten by
        the later real write (write order j=0..A-1), and their cache rows
        sink into the null block."""
        rec = obs_registry.get_recorder()
        a = self.prefill_batch
        pad = a - len(group)
        toks = np.zeros((a, bucket), np.int32)
        lengths = np.ones((a,), np.int32)
        slots_arr = np.full((a,), group[0][1], np.int32)
        tables = np.zeros((a, self.blocks_per_slot), np.int32)
        frames = patches = None
        if self.cfg.encoder_layers:
            frames = np.zeros((a, self.cfg.encoder_frames,
                               self.cfg.d_model), np.float32)
        if self.cfg.patch_tokens:
            patches = np.zeros((a, self.cfg.patch_tokens,
                                self.cfg.d_model), np.float32)
        for j, (req, slot_i, blocks) in enumerate(group):
            r = pad + j
            res = self.results[req.rid]
            sl = self.slots[slot_i]
            sl.state = SlotState.PREFILL
            sl.request = req
            sl.result = res
            sl.key = jax.random.fold_in(self._base_key, req.rid)
            res.t_admit = self.clock()
            self.queue_hist.observe(res.queue_wait)
            rec.histogram("serve.engine.queue_wait_s").observe(
                res.queue_wait)
            toks[r, :req.prompt_len] = req.tokens
            lengths[r] = req.prompt_len
            slots_arr[r] = slot_i
            self._slot_blocks[slot_i] = blocks
            self.block_tables[slot_i, :] = 0
            self.block_tables[slot_i, :len(blocks)] = blocks
            tables[r] = self.block_tables[slot_i]
            if req.frames is not None:
                frames[r] = req.frames
            if req.patches is not None:
                patches[r] = req.patches
        batch = {"tokens": jnp.asarray(toks)}
        if frames is not None:
            batch["frames"] = jnp.asarray(frames)
        if patches is not None:
            batch["patches"] = jnp.asarray(patches)
        with rec.span("engine.prefill", rids=[r.rid for r, _, _ in group],
                      slots=[s for _, s, _ in group], plen=bucket,
                      admitted=len(group)):
            with sh.use_mesh(self.mesh, self.rules):
                logits, self.state = self._prefill(
                    self.params, batch, jnp.asarray(lengths),
                    jnp.asarray(slots_arr), jnp.asarray(tables), self.state)
            for j, (req, slot_i, _) in enumerate(group):
                sl = self.slots[slot_i]
                tok = self._select(logits[pad + j, -1], sl)
                sl.result.tokens.append(tok)
                sl.result.t_first = self.clock()
                self._next_tok[slot_i] = tok
                self._finish_if_done(slot_i, sl)
                if sl.state is SlotState.PREFILL:
                    sl.state = SlotState.DECODE

    def _finish_if_done(self, i: int, sl: _Slot):
        if len(sl.result.tokens) >= sl.request.max_new:
            sl.result.t_done = self.clock()
            sl.state = SlotState.DRAIN
            self.lat_hist.observe(sl.result.latency)
            obs_registry.get_recorder().histogram(
                "serve.engine.latency_s").observe(sl.result.latency)

    def step(self) -> int:
        """One engine step: admissions, then one masked decode over every
        slot. Returns the number of live slots that emitted a token."""
        self._admit()
        active = np.array([sl.state is SlotState.DECODE
                           for sl in self.slots])
        self.peak_active = max(self.peak_active, int(active.sum()))
        if not active.any():
            return 0
        rec = obs_registry.get_recorder()
        with rec.span("engine.decode", active=int(active.sum())):
            with sh.use_mesh(self.mesh, self.rules):
                if self.paged:
                    # block tables ride along as a fresh host->device arg
                    # every step: fixed (slots, blocks_per_slot) shape, so
                    # table churn never retraces the decode program.
                    logits, self.state = self._decode(
                        self.params, jnp.asarray(self._next_tok[:, None]),
                        self.state, jnp.asarray(active),
                        jnp.asarray(self.block_tables))
                else:
                    logits, self.state = self._decode(
                        self.params, jnp.asarray(self._next_tok[:, None]),
                        self.state, jnp.asarray(active))
        self.step_count += 1
        emitted = 0
        last = logits[:, -1]
        if self.greedy:   # one batched argmax + one transfer per step,
            sel = np.asarray(jnp.argmax(last, axis=-1))  # not one per slot
        for i, sl in enumerate(self.slots):
            if not active[i]:
                continue
            tok = int(sel[i]) if self.greedy else self._select(last[i], sl)
            sl.result.tokens.append(tok)
            self._next_tok[i] = tok
            emitted += 1
            self._finish_if_done(i, sl)
        return emitted

    # -- drivers ------------------------------------------------------------

    def busy(self) -> bool:
        return bool(self.queue) or any(
            sl.state in (SlotState.PREFILL, SlotState.DECODE, SlotState.DRAIN)
            for sl in self.slots)

    def drain(self) -> List[RequestResult]:
        """Run until queue and slots are empty; results in rid order."""
        while self.busy():
            self.step()
        return [self.results[rid] for rid in sorted(self.results)]

    def run(self, requests: Iterable[Request], *,
            realtime: bool = False) -> List[RequestResult]:
        """Drain a request stream. With realtime=True each request is held
        back until wall clock passes its `arrival` offset (Poisson arrivals
        from `synth_request_stream`); otherwise requests are submitted in
        arrival order and admission is governed purely by slot pressure."""
        pending = sorted(requests, key=lambda r: r.arrival)
        t0 = self.clock()
        while pending or self.busy():
            now = self.clock() - t0
            while pending and (not realtime or pending[0].arrival <= now):
                r = pending[0]
                self.submit(r.tokens, r.max_new, frames=r.frames,
                            patches=r.patches, arrival=r.arrival)
                pending.pop(0)
            if self.busy():
                self.step()
            elif pending:
                time.sleep(min(0.001, pending[0].arrival - now))
        return [self.results[rid] for rid in sorted(self.results)]

    def stats(self) -> dict:
        """Aggregate serving stats. The key set is STABLE: every key is
        present on an empty engine too (latencies as None, counters as 0)
        — downstream consumers (scenario harness, nightly diff) index the
        schema unconditionally, so it must never shrink with traffic.

        Latency quantiles come from the engine's fixed-bucket histogram
        (`repro.obs.metrics.Histogram`, DESIGN §12): p50/p99 are bucket
        upper edges clamped into the exact [min, max] envelope (~12%
        resolution), mean and max are exact. `queue_wait_mean_s` averages
        over *admitted* requests (it is observed at admission time)."""
        done = [r for r in self.results.values() if r.t_done is not None]
        h = self.lat_hist
        paged_keys = {
            "paged": self.paged,
            "block_size": self.block_size,
            "num_blocks": self.num_blocks,
            "blocks_in_use": self.allocator.used if self.paged else None,
            "peak_blocks": self.allocator.peak if self.paged else None,
        }
        if not done:
            return {
                "requests": 0, "tokens": 0, "tok_per_s": 0.0,
                "latency_mean_s": None, "latency_p50_s": None,
                "latency_p99_s": None, "latency_max_s": None,
                "queue_wait_mean_s": None,
                "decode_steps": self.step_count,
                "peak_active": self.peak_active,
                **paged_keys,
            }
        toks = sum(len(r.tokens) for r in done)
        span = max(r.t_done for r in done) - min(r.t_submit for r in done)
        return {
            "requests": len(done),
            "tokens": toks,
            "tok_per_s": toks / span if span > 0 else float("inf"),
            "latency_mean_s": h.mean,
            "latency_p50_s": h.quantile(0.5),
            "latency_p99_s": h.quantile(0.99),
            "latency_max_s": h.max,
            "queue_wait_mean_s": self.queue_hist.mean,
            "decode_steps": self.step_count,
            "peak_active": self.peak_active,
            **paged_keys,
        }


@dataclasses.dataclass
class WnnResult:
    """One served classification request."""
    rid: int
    scores: np.ndarray                 # (M,) int32 ensemble scores
    pred: int
    t_submit: float
    t_done: Optional[float] = None     # None = queued; see RequestResult

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit


class _WnnLaunch(NamedTuple):
    """One `WnnBatcher` launch from its dispatch until it is delivered."""
    batch: int                         # launch ordinal
    rids: list
    scores: jax.Array                  # (slots, M) on the device
    bits: np.ndarray                   # host batch buffer, kept until done


class WnnBatcher:
    """Micro-batching serve path for WNN artifact inference — the
    classification analogue of `Engine` (DESIGN §2 "Packed layout" /
    §6): requests queue, each `step()` serves up to `slots` of them
    through ONE fixed-shape scores launch over the artifact's prepared
    tables.

    The tables are prepared exactly once (`core.export.prepare_artifact`
    — for the default packed backends that means the uint32 bitplanes go
    in verbatim, never expanded to int8), and the batch function is
    compiled exactly once for `(slots, total_bits)`: partial batches pad
    with zero rows whose outputs are dropped, so admission depth never
    changes the program. `trace_counts` moves only at trace time, like
    `Engine.trace_counts`, so tests can assert the steady state compiles
    nothing.

    With `mesh` the batcher serves class-sharded (DESIGN §7): the
    prepared tables are device_put partitioned over `model` by class
    (replication fallback when M doesn't divide the axis), each batch is
    sharded over the mesh's batch axes, and the one compiled launch
    computes per-shard partial score columns plus the gathered (B, M)
    argmax. Still exactly one compile — the mesh changes placement, not
    shapes — and bit-identical int32 scores to the unsharded batcher.

    At most one launch is in flight, the same with or without a mesh:
    `step()` assembles, transfers and dispatches launch k, starts its
    scores' copy to the host, and only then waits for and delivers launch
    k-1, whose device work ran under the host work since the last step.
    What to do with launch k is read from the queue: if rows are still
    queued after its take it stays in flight; if the take emptied the
    queue, the step waits for and delivers it too. So whenever `queue` is
    empty after `step()`, nothing is in flight and every submitted row
    has its answer; at low load, where each step takes all that is
    queued, a step serves its own launch. A launch keeps its rids, its
    device scores and its host batch buffer until delivered, and every
    launch allocates a fresh buffer, so none is written while the device
    may still read it. `stats()["overlapped"]` counts the launches whose
    wait found their scores already computed.

        batcher = WnnBatcher(artifact, slots=64, backend="auto")
        rid = batcher.submit(encoded_bits_row)
        results = batcher.drain()      # -> [WnnResult]
    """

    def __init__(self, artifact, *, slots: int = 64, backend: str = "auto",
                 mesh=None, clock: Callable = None):
        from repro.core import export as export_mod
        if slots < 1:
            raise ValueError("need slots >= 1")
        self.artifact = artifact
        self.slots = slots
        self.backend = backend
        self.mesh = mesh
        self.rules = sh.SERVE_RULES
        self.total_bits = int(artifact.total_bits)
        self.clock = clock or time.perf_counter
        self._prep = export_mod.prepare_artifact(artifact, backend=backend,
                                                 mesh=mesh, rules=self.rules)
        self.class_shards = 1 if mesh is None else sh.class_partition(
            mesh, int(artifact.num_classes), self.rules)[1]
        self.trace_counts: collections.Counter = collections.Counter()
        self.lat_hist = obs_metrics.Histogram()

        def _batch_scores(prep, bits):
            # THE serve loop, shared with artifact_scores — semantics
            # cannot drift between the one-shot and batch paths. The
            # predict tail gathers the class-sharded partial columns
            # into the full (B, M) matrix on device (a no-op unsharded).
            scores, _ = export_mod.predict_from_prep(prep, bits,
                                                     backend=backend)
            return scores

        _batch_scores = obs_jaxhooks.counted(
            _batch_scores, self.trace_counts, "batch_scores")

        if mesh is None:
            self._scores = jax.jit(_batch_scores)
            self._bits_sharding = None
        else:
            pshard = export_mod.prep_shardings(self._prep, mesh, self.rules)
            self._bits_sharding = sh.named_sharding(
                mesh, self.rules, ("batch", None),
                shape=(slots, self.total_bits))
            self._scores = jax.jit(
                _batch_scores, in_shardings=(pshard, self._bits_sharding))
        self.queue: collections.deque = collections.deque()
        self.results: dict = {}
        self._next_rid = 0
        self._in_flight: Optional[_WnnLaunch] = None
        self.batches = 0
        self.served = 0
        self.overlapped = 0

    def submit(self, bits) -> int:
        """Queue one encoded input (total_bits,) {0,1}; returns its rid."""
        bits = np.asarray(bits).reshape(-1)
        if bits.shape[0] != self.total_bits:
            raise ValueError(f"request has {bits.shape[0]} bits, artifact "
                             f"encodes {self.total_bits}")
        rid = self._next_rid
        self._next_rid += 1
        self.results[rid] = WnnResult(rid=rid, scores=None, pred=-1,
                                      t_submit=self.clock())
        self.queue.append((rid, bits.astype(np.uint8)))
        return rid

    def step(self) -> int:
        """Launch up to `slots` queued requests in one fixed-shape call and
        complete the launch before it (both, if the take empties the
        queue); returns the rows launched, 0 when idle.

        Spans, one tree per launch: `wnn.step` (attrs `batch`, the launch
        ordinal, and `take`) over `wnn.assemble` (the batch buffer, queue
        pops, row copies), `wnn.batch` and one `wnn.deliver` per launch
        completed (the per-row results); `wnn.batch` over `wnn.transfer`
        (the batch to the device), `wnn.dispatch` (the jitted call until
        it returns) and one `wnn.wait` per launch completed (its scores
        on the host; attr `ready`, whether the device had finished when
        the wait began). `wnn.wait` and `wnn.deliver` carry `batch`, the
        ordinal of the launch they complete."""
        rec = obs_registry.get_recorder()
        if not self.queue:
            if self._in_flight is not None:    # only after a step that raised
                launch, self._in_flight = self._in_flight, None
                self._deliver(rec, launch, self._wait(rec, launch))
            return 0
        take = min(self.slots, len(self.queue))
        with rec.span("wnn.step", batch=self.batches, take=take):
            with rec.span("wnn.assemble"):
                batch = np.zeros((self.slots, self.total_bits), np.uint8)
                rids = []
                for i in range(take):
                    rid, bits = self.queue.popleft()
                    batch[i] = bits
                    rids.append(rid)
            with rec.span("wnn.batch", take=take):
                if self.mesh is None:
                    with rec.span("wnn.transfer"):
                        bits = jnp.asarray(batch)
                    with rec.span("wnn.dispatch"):
                        scores = self._scores(self._prep, bits)
                else:
                    with sh.use_mesh(self.mesh, self.rules):
                        with rec.span("wnn.transfer"):
                            bits = jax.device_put(batch, self._bits_sharding)
                        with rec.span("wnn.dispatch"):
                            scores = self._scores(self._prep, bits)
                scores.copy_to_host_async()
                launch = _WnnLaunch(self.batches, rids, scores, batch)
                due = [] if self._in_flight is None else [self._in_flight]
                if self.queue:
                    self._in_flight = launch
                else:
                    self._in_flight = None
                    due.append(launch)
                done = [(d, self._wait(rec, d)) for d in due]
            for d, host_scores in done:
                self._deliver(rec, d, host_scores)
        self.batches += 1
        self.served += take
        return take

    def _wait(self, rec, launch: _WnnLaunch) -> np.ndarray:
        """`launch`'s scores on the host."""
        ready = launch.scores.is_ready()
        self.overlapped += int(ready)
        with rec.span("wnn.wait", batch=launch.batch, ready=ready):
            return np.asarray(launch.scores)

    def _deliver(self, rec, launch: _WnnLaunch, scores: np.ndarray):
        """Each of `launch`'s rows gets its scores, answer and `t_done`."""
        with rec.span("wnn.deliver", batch=launch.batch):
            t = self.clock()
            for i, rid in enumerate(launch.rids):
                res = self.results[rid]
                res.scores = scores[i]
                res.pred = int(np.argmax(scores[i]))
                res.t_done = t
                self.lat_hist.observe(res.latency)

    def drain(self) -> List[WnnResult]:
        """Serve until the queue is empty; results in rid order."""
        while self.queue:
            self.step()
        return [self.results[rid] for rid in sorted(self.results)]

    def stats(self) -> dict:
        """Batch-serving stats; stable key set (latencies None when
        nothing finished yet — the schema never shrinks, like
        `Engine.stats`). Quantiles come from the fixed-bucket latency
        histogram (DESIGN §12): bucket-resolution p50/p99, exact
        mean/max."""
        done = [r for r in self.results.values() if r.t_done is not None]
        occupancy = self.served / max(1, self.batches * self.slots)
        h = self.lat_hist
        return {"requests": len(done), "batches": self.batches,
                "submitted": self._next_rid, "served": self.served,
                "queued": len(self.queue),
                "overlapped": self.overlapped,
                "class_shards": self.class_shards,
                "occupancy": occupancy,
                "traces": int(self.trace_counts["batch_scores"]),
                "latency_mean_s": h.mean,
                "latency_p50_s": h.quantile(0.5),
                "latency_p99_s": h.quantile(0.99),
                "latency_max_s": h.max}


@dataclasses.dataclass
class WnnTenantResult:
    """One served multi-tenant classification request."""
    rid: int
    tid: int                           # tenant the request was routed to
    scores: np.ndarray                 # (M,) int32 ensemble scores
    pred: int
    t_submit: float
    t_done: Optional[float] = None     # None = queued; see RequestResult

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit


class WnnTenantBatcher:
    """Tenant-routed micro-batching over a fleet of same-geometry WNN
    artifacts (DESIGN §11) — `WnnBatcher` grown a tenant axis.

    Thousands of KB-scale artifacts register via `add_tenant`; at most
    `capacity` of them are *resident* at once in one device-side
    `StackedPackedTables` cache (`packed.stacked_zeros` slots). Requests
    carry a tenant id; each `step()` routes up to `slots` of them through
    ONE fixed-shape `stacked_predict` launch — the batch rows index their
    tenant's tables by slot id, so neither admission depth nor WHICH
    tenants are in the batch ever changes the compiled program
    (`trace_counts["batch_scores"]` pins exactly one trace, like
    `WnnBatcher`; slot installs are one more fixed-shape program).

    Admission/eviction is LRU: a request for a non-resident tenant
    installs that tenant's prepared tables (`core.export.prepare_artifact`
    — cached, so a tenant re-admitted after eviction never re-packs) into
    the least-recently-used slot whose tenant is not referenced by the
    current batch. When every slot is pinned by the batch being formed,
    the request defers to the queue head for the next step — so a batch
    can never need more distinct tenants than `capacity`, and `drain()`
    always terminates (the first request of a step always admits).

    With `mesh` the batch shards over the mesh's batch axes while the
    resident stack replicates — per-tenant tables are KB-scale, which is
    the point; the *static* N-thousand-tenant fleet partitioned over
    `model` is the dryrun cell's regime (`uleen_cell.
    lower_uleen_multitenant_infer_cell`), not the hot-cache batcher's.

        batcher = WnnTenantBatcher(capacity=64, slots=32)
        tid = batcher.add_tenant(artifact)
        rid = batcher.submit(tid, encoded_bits_row)
        results = batcher.drain()      # -> [WnnTenantResult]
    """

    def __init__(self, *, capacity: int = 64, slots: int = 64,
                 backend: str = "auto", mesh=None, clock: Callable = None):
        if capacity < 1:
            raise ValueError("need capacity >= 1")
        if slots < 1:
            raise ValueError("need slots >= 1")
        if backend not in ("packed", "auto"):
            raise ValueError(
                f"the tenant batcher serves the packed domain only "
                f"(backend='packed'|'auto', got {backend!r})")
        self.capacity = capacity
        self.slots = slots
        self.backend = backend
        self.mesh = mesh
        self.rules = sh.SERVE_RULES
        self.clock = clock or time.perf_counter
        self.trace_counts: collections.Counter = collections.Counter()
        self.lat_hist = obs_metrics.Histogram()

        self.total_bits: Optional[int] = None
        self._tenants: list = []           # tid -> prepared PackedTables
        self._artifacts: list = []         # keep prep cache owners alive
        self._stack = None                 # device StackedPackedTables
        self._resident: dict = {}          # tid -> slot
        self._slot_tid: list = [None] * capacity
        self._lru: collections.OrderedDict = collections.OrderedDict()
        self._scores = None
        self._install = None
        self._bits_sharding = None
        self._sids_sharding = None

        self.queue: collections.deque = collections.deque()
        self.results: dict = {}
        self._next_rid = 0
        self.batches = 0
        self.served = 0
        self.admissions = 0
        self.evictions = 0
        self.hits = 0
        self.misses = 0
        self.per_tenant: dict = {}

    # -- fleet registry -----------------------------------------------------

    def add_tenant(self, artifact) -> int:
        """Register one artifact; returns its tenant id. The first tenant
        fixes the fleet geometry — later artifacts must match it exactly
        (entries, classes, per-submodel shapes), the same trace-time
        guarantee `packed.stack_tenants` enforces."""
        from repro.core import export as export_mod
        from repro.packed import layout
        prep = export_mod.prepare_artifact(artifact, backend=self.backend)
        if self._stack is None:
            self.total_bits = int(artifact.total_bits)
            self._build(prep)
        else:
            tmpl = self._tenants[0]
            if (prep.entries != tmpl.entries
                    or prep.num_classes != tmpl.num_classes
                    or int(artifact.total_bits) != self.total_bits
                    or any(a.shape != b.shape for a, b in
                           zip(prep.words, tmpl.words))
                    or any(a.shape != b.shape for a, b in
                           zip(prep.perms, tmpl.perms))):
                raise ValueError(
                    f"tenant {len(self._tenants)} geometry does not match "
                    f"the fleet's (entries {prep.entries} vs {tmpl.entries}, "
                    f"M {prep.num_classes} vs {tmpl.num_classes}) — stacked "
                    "tenants must share geometry")
        tid = len(self._tenants)
        self._tenants.append(prep)
        self._artifacts.append(artifact)
        # per-tenant latency is a fixed-bucket histogram, not a raw list:
        # on a long-lived server the old lists grew with *traffic*
        # per tenant, unbounded (DESIGN §12)
        self.per_tenant[tid] = {"requests": 0, "batches": 0,
                                "hist": obs_metrics.Histogram()}
        return tid

    def _build(self, template):
        """One-time device cache + compiled-program construction, driven
        by the first tenant's geometry."""
        from repro.packed import layout, runtime
        backend = self.backend
        stack = layout.stacked_zeros(template, self.capacity)

        def _batch_scores(st, bits, sids):
            # slot-indexed fleet scoring — THE serve loop of the stacked
            # path, shared with the dryrun cell via stacked_predict
            scores, _ = runtime.stacked_predict(st, bits, sids,
                                                backend=backend)
            return scores

        _batch_scores = obs_jaxhooks.counted(
            _batch_scores, self.trace_counts, "batch_scores")

        def _install(st, pt, slot):
            return layout.StackedPackedTables(
                words=tuple(w.at[slot].set(v)
                            for w, v in zip(st.words, pt.words)),
                masks=tuple(m.at[slot].set(v)
                            for m, v in zip(st.masks, pt.masks)),
                perms=tuple(p.at[slot].set(v)
                            for p, v in zip(st.perms, pt.perms)),
                h3s=tuple(h.at[slot].set(v)
                          for h, v in zip(st.h3s, pt.h3s)),
                bias=st.bias.at[slot].set(pt.bias),
                entries=st.entries, num_classes=st.num_classes,
                num_tenants=st.num_tenants)

        self._install = jax.jit(
            obs_jaxhooks.counted(_install, self.trace_counts, "install"),
            donate_argnums=(0,))
        if self.mesh is None:
            self._stack = stack
            self._scores = jax.jit(_batch_scores)
        else:
            rep = sh.named_sharding(self.mesh, self.rules, ())
            self._stack = jax.device_put(
                stack, jax.tree.map(lambda _: rep, stack))
            self._bits_sharding = sh.named_sharding(
                self.mesh, self.rules, ("batch", None),
                shape=(self.slots, self.total_bits))
            self._sids_sharding = sh.named_sharding(
                self.mesh, self.rules, ("batch",), shape=(self.slots,))
            self._scores = jax.jit(
                _batch_scores,
                in_shardings=(jax.tree.map(lambda _: rep, stack),
                              self._bits_sharding, self._sids_sharding))

    # -- serving ------------------------------------------------------------

    def submit(self, tid: int, bits) -> int:
        """Queue one encoded input for tenant `tid`; returns its rid."""
        if not 0 <= tid < len(self._tenants):
            raise ValueError(
                f"unknown tenant {tid}; registered: {len(self._tenants)}")
        bits = np.asarray(bits).reshape(-1)
        if bits.shape[0] != self.total_bits:
            raise ValueError(f"request has {bits.shape[0]} bits, the fleet "
                             f"encodes {self.total_bits}")
        rid = self._next_rid
        self._next_rid += 1
        self.results[rid] = WnnTenantResult(rid=rid, tid=tid, scores=None,
                                            pred=-1,
                                            t_submit=self.clock())
        self.queue.append((rid, tid, bits.astype(np.uint8)))
        return rid

    def _admit(self, tid: int, batch_tenants: set) -> Optional[int]:
        """Install tenant `tid` into a slot: a free one, else the LRU
        resident not pinned by the forming batch. None when every slot is
        pinned (caller defers the request)."""
        rec = obs_registry.get_recorder()
        free = [s for s, t in enumerate(self._slot_tid) if t is None]
        if free:
            slot = free[0]
        else:
            victim = next((t for t in self._lru if t not in batch_tenants),
                          None)
            if victim is None:
                return None
            slot = self._resident.pop(victim)
            del self._lru[victim]
            self.evictions += 1
            rec.counter("serve.tenant.eviction").inc()
        with rec.span("tenant.install", tid=tid, slot=slot):
            self._stack = self._install(self._stack, self._tenants[tid],
                                        jnp.asarray(slot, jnp.int32))
        self._slot_tid[slot] = tid
        self._resident[tid] = slot
        self.admissions += 1
        rec.counter("serve.tenant.admission").inc()
        return slot

    def step(self) -> int:
        """Serve up to `slots` queued requests in one fixed-shape launch,
        admitting/evicting tenants as needed; returns the number served.
        Requests whose tenant cannot be made resident alongside this
        batch's tenants defer (in order) to the queue head."""
        if not self.queue:
            return 0
        rec = obs_registry.get_recorder()
        take: list = []
        deferred: list = []
        batch_tenants: set = set()
        while self.queue and len(take) < self.slots:
            rid, tid, bits = self.queue.popleft()
            slot = self._resident.get(tid)
            if slot is not None:
                self.hits += 1
                rec.counter("serve.tenant.cache_hit").inc()
            else:
                slot = self._admit(tid, batch_tenants)
                if slot is None:
                    # deferred, not a miss: the retry re-decides, so
                    # hits + misses always equals requests served
                    deferred.append((rid, tid, bits))
                    continue
                self.misses += 1
                rec.counter("serve.tenant.cache_miss").inc()
            batch_tenants.add(tid)
            take.append((rid, tid, bits, slot))
        for item in reversed(deferred):
            self.queue.appendleft(item)

        batch = np.zeros((self.slots, self.total_bits), np.uint8)
        sids = np.zeros((self.slots,), np.int32)
        for i, (_rid, _tid, bits, slot) in enumerate(take):
            batch[i] = bits
            sids[i] = slot
        with rec.span("wnn.tenant_batch", take=len(take),
                      tenants=len(batch_tenants)):
            if self.mesh is None:
                scores = np.asarray(self._scores(
                    self._stack, jnp.asarray(batch), jnp.asarray(sids)))
            else:
                with sh.use_mesh(self.mesh, self.rules):
                    scores = np.asarray(self._scores(
                        self._stack,
                        jax.device_put(batch, self._bits_sharding),
                        jax.device_put(sids, self._sids_sharding)))
        t = self.clock()
        lat_hist_global = rec.histogram("serve.tenant.latency_s")
        for i, (rid, tid, _bits, _slot) in enumerate(take):
            res = self.results[rid]
            res.scores = scores[i]
            res.pred = int(np.argmax(scores[i]))
            res.t_done = t
            self.lat_hist.observe(res.latency)
            lat_hist_global.observe(res.latency)
            pt = self.per_tenant[tid]
            pt["requests"] += 1
            pt["hist"].observe(res.latency)
        for tid in batch_tenants:
            self.per_tenant[tid]["batches"] += 1
            self._lru[tid] = None
            self._lru.move_to_end(tid)    # most recently used -> tail
        self.batches += 1
        self.served += len(take)
        return len(take)

    def drain(self) -> List[WnnTenantResult]:
        """Serve until the queue is empty; results in rid order."""
        while self.queue:
            self.step()
        return [self.results[rid] for rid in sorted(self.results)]

    def stats(self) -> dict:
        """Fleet-serving stats; stable key set (latencies None when
        nothing finished — the schema never shrinks), plus a per-tenant
        breakdown: requests, latency mean/p50, launches the tenant rode
        in, and its occupancy share of total launch capacity."""
        done = [r for r in self.results.values() if r.t_done is not None]
        h = self.lat_hist
        out = {"requests": len(done), "batches": self.batches,
               "submitted": self._next_rid, "served": self.served,
               "queued": len(self.queue),
               "tenants": len(self._tenants),
               "capacity": self.capacity,
               "resident": len(self._resident),
               "admissions": self.admissions,
               "evictions": self.evictions,
               "hits": self.hits, "misses": self.misses,
               "occupancy": self.served / max(1, self.batches * self.slots),
               "traces": int(self.trace_counts["batch_scores"]),
               "install_traces": int(self.trace_counts["install"]),
               "latency_mean_s": h.mean,
               "latency_p50_s": h.quantile(0.5),
               "latency_p99_s": h.quantile(0.99),
               "latency_max_s": h.max,
               "per_tenant": {}}
        cap = max(1, self.batches * self.slots)
        for tid, pt in self.per_tenant.items():
            th = pt["hist"]
            out["per_tenant"][tid] = {
                "requests": pt["requests"],
                "batches": pt["batches"],
                "occupancy": pt["requests"] / cap,
                "latency_mean_s": th.mean,
                "latency_p50_s": th.quantile(0.5),
                "latency_p99_s": th.quantile(0.99),
            }
        return out


def synth_request_stream(cfg: ArchConfig, n: int, *, rate: float = 32.0,
                         seed: int = 0, prompt_lens=(8, 16, 24),
                         gen_lens=(4, 8, 16)) -> List[Request]:
    """n synthetic requests with Poisson arrivals (exponential gaps at
    `rate` req/s) and mixed prompt/generation lengths — the CLI's --stream
    workload and the service smoke test's traffic model."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for _ in range(n):
        t += float(rng.exponential(1.0 / rate))
        plen = int(rng.choice(prompt_lens))
        req = Request(
            tokens=rng.integers(0, cfg.vocab_size, size=(plen,),
                                dtype=np.int32),
            max_new=int(rng.choice(gen_lens)), arrival=t)
        if cfg.encoder_layers:
            req.frames = (rng.standard_normal(
                (cfg.encoder_frames, cfg.d_model)) * 0.02).astype(np.float32)
        if cfg.patch_tokens:
            req.patches = (rng.standard_normal(
                (cfg.patch_tokens, cfg.d_model)) * 0.02).astype(np.float32)
        out.append(req)
    return out
