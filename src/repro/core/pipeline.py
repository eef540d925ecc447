"""Batched on-board serving pipeline.

The paper's PYNQ flow is load_ip_input() -> start_ip() -> read_ip_output(),
with Fig 11 showing input staging *dominating* inference for small models.
This pipeline reproduces that phase structure and fixes it the way a real
deployment would: a pool of reusable host staging buffers (batch k+1 is
assembled while batch k computes), non-blocking dispatch tickets riding
JAX's async dispatch, and micro-batching, with per-phase timing so the
staging/compute overlap is measurable.

It also implements the use cases' *decision* layer: selective downlink —
requests whose model output crosses the trigger predicate are kept
(e.g. MMS region-of-interest, ESPERTA warnings), everything else is
dropped, and the achieved downlink-reduction ratio is reported (the
paper's motivating metric).

``ServingPipeline`` is the *single-model, single-batch-size core*: one
compiled plan, one padded batch per call. The continuous-batching
scheduler (core/scheduler.py) composes one pipeline per ladder rung and
drives :meth:`execute_batch` (or :meth:`execute_batch_async` in pipelined
mode) per dispatch; :meth:`run` is the standalone fixed-batch streaming
mode over a pre-materialized request list.

Synchronization contract (DESIGN.md §12): no path here ever calls
``jax.block_until_ready``. A dispatch's outputs are forced — one
``np.asarray`` per output, which blocks on exactly that batch — when its
:class:`DispatchTicket` retires: immediately in :meth:`execute_batch`,
lazily (slot-pool exhaustion, stream end, or an explicit :meth:`sync`
telemetry barrier) in the pipelined paths. The scheduler's wall-clock
dispatcher also retires a ticket as soon as :meth:`DispatchTicket.ready`
says its outputs are done, whenever it has nothing to dispatch.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import (Callable, Deque, Dict, Iterable, List, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import memory as memory_mod
from repro.core import spans


@dataclasses.dataclass
class PhaseTimes:
    stage_in: float = 0.0
    compute: float = 0.0
    stage_out: float = 0.0
    overlapped: float = 0.0         # wall time saved by pipelining

    @property
    def serial(self) -> float:
        return self.stage_in + self.compute + self.stage_out

    @property
    def wall(self) -> float:
        return self.serial - self.overlapped


@dataclasses.dataclass
class ServeStats:
    n_requests: int
    n_kept: int
    phases: PhaseTimes
    fps: float

    @property
    def downlink_reduction(self) -> float:
        return 1.0 - self.n_kept / max(self.n_requests, 1)


@dataclasses.dataclass
class BatchResult:
    """One dispatched batch: host outputs sliced back to the real requests,
    the per-request selective-downlink verdicts, and per-phase timings.
    ``compute_time`` spans dispatch to retirement (it includes the async
    wait when the ticket retired late)."""
    outputs: Dict[str, np.ndarray]      # [n_real, ...] — padding sliced off
    keep: List[bool]                    # per real request
    stage_time: float
    compute_time: float
    output_time: float

    @property
    def n_kept(self) -> int:
        return sum(self.keep)


def stage_batch(reqs: List[Dict[str, np.ndarray]], batch_size: int
                ) -> Dict[str, jax.Array]:
    """Stack request dicts into one ``[batch_size, ...]`` device batch,
    padding a ragged tail by repeating the last sample (the padding rows
    are sliced off after compute). The freshly-allocating fallback of the
    arena staging path below — and the reference its bit-exactness is
    tested against.

    Assembly is host-side NumPy on purpose: staging must cost one device
    transfer, never an XLA compile — jnp stacking would recompile for
    every distinct ragged length the scheduler flushes."""
    if not reqs:
        raise ValueError("stage_batch needs at least one request")
    if len(reqs) > batch_size:
        raise ValueError(f"{len(reqs)} requests > batch size {batch_size}")
    batch = {k: np.stack([np.asarray(r[k], np.float32) for r in reqs])
             for k in reqs[0]}
    if len(reqs) < batch_size:             # pad the ragged tail
        pad = batch_size - len(reqs)
        batch = {k: np.concatenate(
            [v, np.repeat(v[-1:], pad, axis=0)]) for k, v in batch.items()}
    return jax.device_put(batch)


class HostStagingArena:
    """The pool of reusable host batch buffers a :class:`StagingPlan`
    sizes: ``slots`` preallocated fp32 ``[B, ...]`` NumPy buffers per
    host-staged graph input, filled in place per dispatch instead of
    re-allocating a fresh stack for every ``jax.device_put``. Inputs the
    plan hands to the runtime directly have no buffer here, but a
    dispatch still owns a slot: the pool bounds the dispatches in flight.

    Donation invariant (DESIGN.md §12): ``acquire()`` transfers slot
    ownership to the dispatch being staged; the slot returns to the free
    pool only when that dispatch's ticket retires. ``jax.device_put``
    may alias host memory on CPU backends, so an owned slot is NEVER
    rewritten while its batch is in flight. ``stage()`` writes every row
    (real rows then ragged padding), so slot reuse can never leak a
    previous batch's samples."""

    def __init__(self, staging: memory_mod.StagingPlan):
        self.staging = staging
        self._bufs = [
            {k: np.empty(shape, np.float32)
             for k, shape in staging.input_shapes.items()}
            for _ in range(staging.slots)]
        self._free: Deque[int] = deque(range(staging.slots))
        self.n_staged = 0           # dispatches staged through a slot
        self.n_fallback = 0         # pool-exhausted fresh allocations

    @property
    def n_slots(self) -> int:
        return self.staging.slots

    @property
    def n_free(self) -> int:
        return len(self._free)

    def acquire(self) -> Optional[int]:
        """Take a free slot (None when the pool is exhausted — callers
        fall back to a fresh `stage_batch` allocation, never deadlock)."""
        return self._free.popleft() if self._free else None

    def release(self, slot: int) -> None:
        self._free.append(slot)

    def stage(self, slot: int, reqs: List[Dict[str, np.ndarray]]
              ) -> Dict[str, np.ndarray]:
        """Fill ``slot``'s host-staged buffers in place with ``reqs``
        (+ repeat-last padding); returns the slot's buffer dict.
        Bit-identical to `stage_batch`: the same fp32 casts, the same
        padding rule."""
        n = len(reqs)
        bufs = self._bufs[slot]
        for k, buf in bufs.items():
            for i, r in enumerate(reqs):
                buf[i] = np.asarray(r[k], np.float32)
            if n < self.staging.batch_size:
                buf[n:] = buf[n - 1]
        self.n_staged += 1
        return bufs


@dataclasses.dataclass
class DispatchTicket:
    """One in-flight dispatched batch: unforced device outputs plus the
    staging slot the dispatch owns. ``retire()`` forces the outputs to
    host (np.asarray — blocks on exactly this batch), runs the keep
    predicate, releases the slot back to the pool, and returns the
    :class:`BatchResult`. Idempotent: later calls return the cached
    result.

    Failure contract: if forcing the outputs or the keep predicate
    raises, the staging slot is STILL released and the ticket unlinked
    (a pool slot must never leak with its dispatch — the old leak
    silently drained the pool into the ``n_fallback`` path forever); the
    ticket is left poisoned, so a later ``retire()`` raises RuntimeError
    instead of fabricating a result.

    ``rows`` holds the host rows handed to the runtime directly (not
    copied into the slot) until retirement: a submitted input must not
    be freed or written while its batch is in flight."""
    pipeline: "ServingPipeline"
    outputs: Optional[Dict[str, jax.Array]]
    n_real: int
    slot: Optional[int]
    stage_time: float
    dispatched_at: float                # perf_counter at dispatch
    rows: Tuple[np.ndarray, ...] = ()
    _result: Optional[BatchResult] = None

    @property
    def retired(self) -> bool:
        return self._result is not None

    def ready(self) -> bool:
        """Whether ``retire()`` would return without waiting on the
        device: every output reports ``is_ready()`` (a host value, which
        has no such method, is ready), or the ticket already retired or
        failed. Forces and copies nothing."""
        if self._result is not None or self.outputs is None:
            return True
        return all(v.is_ready() for v in self.outputs.values()
                   if hasattr(v, "is_ready"))

    def _release(self) -> None:
        self.rows = ()
        if self.slot is not None:
            self.pipeline.arena.release(self.slot)
            self.slot = None
        try:
            self.pipeline._inflight.remove(self)
        except ValueError:
            pass

    def retire(self) -> BatchResult:
        if self._result is not None:
            return self._result
        if self.outputs is None:
            raise RuntimeError(
                "retire() after a failed retirement: this ticket's batch "
                "was already abandoned (its outputs are gone)")
        try:
            with spans.span("serve.retire.fetch"):
                host_out = self.pipeline._unstage(self.outputs, self.n_real)
            t1 = time.perf_counter()
            keep = self.pipeline._keep(host_out, self.n_real)
            t2 = time.perf_counter()
        except BaseException:
            self.outputs = None         # poison: no result can ever exist
            self._release()
            raise
        self.outputs = {}               # drop the device references
        self._release()
        self._result = BatchResult(
            host_out, keep, stage_time=self.stage_time,
            compute_time=t1 - self.dispatched_at, output_time=t2 - t1)
        return self._result


class ServingPipeline:
    """Micro-batched, pipelined inference over a request stream.

    Uses the engine's staged plan cache: ONE compiled batched executable
    per (backend, batch_size), built up front — the serving loop never
    re-traces. Ragged final chunks are padded up to the plan's batch size
    (and the padding sliced off), so a request stream of any length hits
    exactly one executable. ``staging_buffers`` sizes the host staging
    arena (2 = classic double buffering).

    Inputs with large rows (``memory.DIRECT_ROW_BYTES``) skip the arena:
    each request's row is passed as it is to a jitted ``assemble``, whose
    call hands it to the runtime and stacks the batch on the device.
    ``n_rows_direct`` / ``n_rows_staged`` count (input, real row) pairs
    on each path.
    """

    def __init__(self, engine, backend: str = "flex",
                 batch_size: int = 16,
                 keep_predicate: Optional[Callable] = None,
                 staging_buffers: int = 2):
        self.engine = engine
        self.backend = backend
        self.batch_size = batch_size
        self.keep_predicate = keep_predicate
        self._plan = engine.compile(backend, batch_size)
        self.staging = memory_mod.plan_staging(
            self._plan.plan.graph, batch_size, staging_buffers)
        self.arena = HostStagingArena(self.staging)
        self._inflight: Deque[DispatchTicket] = deque()
        self._assemble = jax.jit(self._stack_rows)
        self.n_assemble_traces = 0
        self.n_rows_direct = 0
        self.n_rows_staged = 0

    @property
    def cost(self):
        """The compiled plan's plan-time cost signature (energy/latency/W
        of one full-batch dispatch) — what the scheduler ranks backends by
        and charges the power envelope with."""
        return self._plan.cost

    @property
    def stages(self):
        """The plan's pipeline-stage decomposition (energy.StageCost
        tuple) — what the scheduler's overlap ledger prices dispatches
        with."""
        return self._plan.stages

    def _stack_rows(self, rows: Dict[str, List[np.ndarray]]
                    ) -> Dict[str, jax.Array]:
        """``assemble``'s body: every call passes ``batch_size`` host rows
        per direct input, so it traces and compiles once per pipeline."""
        self.n_assemble_traces += 1         # runs only while tracing
        return {k: jnp.stack(v) for k, v in rows.items()}

    def _direct_rows(self, reqs: List[Dict[str, np.ndarray]]
                     ) -> Dict[str, List[np.ndarray]]:
        """Each direct input's ``batch_size`` rows as submitted (the fp32
        cast of `stage_batch`, which copies nothing for fp32 rows), a
        ragged tail padded by repeating the last row. The runtime then
        transfers that row once per padding slot; in exchange every call
        passes host rows only, the one signature `register`'s warm-up
        compiles (a padding device array would be a second one, met
        first inside a serving window)."""
        rows = {}
        pad = self.batch_size - len(reqs)
        for k, shape in self.staging.direct_shapes.items():
            rows[k] = [np.asarray(r[k], np.float32) for r in reqs]
            for row in rows[k]:
                if row.shape != shape:
                    raise ValueError(f"input {k!r}: row shape {row.shape} "
                                     f"!= planned {shape}")
            rows[k] += rows[k][-1:] * pad
        return rows

    def _stage(self, reqs: List[Dict[str, np.ndarray]]
               ) -> Tuple[Dict[str, jax.Array], Optional[int],
                          Tuple[np.ndarray, ...]]:
        """Stage one batch: host-staged inputs into an arena slot
        (in-place reuse) and one `jax.device_put`; direct inputs by
        passing their host rows to the jitted ``assemble``, whose
        compiled call hands each to the runtime and stacks them on the
        device. Falls back to a fresh `stage_batch` allocation of every
        input when the pool is dry. Returns (device batch, owned slot or
        None, the host rows the runtime was handed)."""
        if not reqs:
            raise ValueError("stage_batch needs at least one request")
        if len(reqs) > self.batch_size:
            raise ValueError(
                f"{len(reqs)} requests > batch size {self.batch_size}")
        n = len(reqs)
        slot = self.arena.acquire()
        if slot is None:
            self.arena.n_fallback += 1
            with spans.span("serve.stage"):
                staged = stage_batch(reqs, self.batch_size)
            self.n_rows_staged += n * len(staged)
            return staged, None, ()
        try:
            with spans.span("serve.stage"):
                host = self.arena.stage(slot, reqs)
                rows = self._direct_rows(reqs)
            with spans.span("serve.transfer"):
                staged = jax.device_put(host)
                if rows:
                    staged.update(self._assemble(rows))
        except BaseException:
            self.arena.release(slot)
            raise
        self.n_rows_staged += n * len(host)
        self.n_rows_direct += n * len(rows)
        return staged, slot, tuple(r for v in rows.values() for r in v[:n])

    def _dispatch(self, staged: Dict[str, jax.Array], rng: jax.Array
                  ) -> Tuple[Dict[str, jax.Array], jax.Array]:
        """One plan call — async dispatch, nothing forced; returns
        (unforced device outputs, carried-over rng)."""
        with spans.span("serve.launch"):
            rngs = jax.random.split(rng, self.batch_size + 1)
            return self._plan(staged, rngs[1:]), rngs[0]

    def _issue(self, staged: Dict[str, jax.Array], slot: Optional[int],
               rows: Tuple[np.ndarray, ...], n_real: int,
               stage_time: float, rng: jax.Array
               ) -> Tuple[DispatchTicket, jax.Array]:
        try:
            out, carry = self._dispatch(staged, rng)
        except BaseException:
            if slot is not None:        # dispatch failed: slot back to pool
                self.arena.release(slot)
            raise
        ticket = DispatchTicket(self, out, n_real, slot, stage_time,
                                time.perf_counter(), rows)
        self._inflight.append(ticket)
        return ticket, carry

    def _unstage(self, out: Dict[str, jax.Array], n_real: int
                 ) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)[:n_real] for k, v in out.items()}

    def _keep(self, host_out: Dict[str, np.ndarray], n_real: int
              ) -> List[bool]:
        if self.keep_predicate is None:
            return [True] * n_real
        return [bool(self.keep_predicate({k: v[i] for k, v in host_out.items()}))
                for i in range(n_real)]

    # -- the scheduler's dispatch core --------------------------------------

    def execute_batch_async(self, reqs: List[Dict[str, np.ndarray]],
                            rng: Optional[jax.Array] = None
                            ) -> DispatchTicket:
        """Stage + dispatch ONE (possibly ragged) batch WITHOUT forcing
        the result: staging is synchronous host work, the plan call rides
        JAX's async dispatch, and the returned ticket owns the staging
        slot until `retire()`."""
        if rng is None:
            rng = jax.random.PRNGKey(0)
        t0 = time.perf_counter()
        staged, slot, rows = self._stage(reqs)
        t1 = time.perf_counter()
        ticket, _ = self._issue(staged, slot, rows, len(reqs), t1 - t0, rng)
        return ticket

    def execute_batch(self, reqs: List[Dict[str, np.ndarray]],
                      rng: Optional[jax.Array] = None) -> BatchResult:
        """Serve exactly ONE (possibly ragged) batch and return its forced
        result: stage + pad -> compiled plan -> slice padding -> keep
        predicate. Synchronous from the caller's view, but with NO
        `jax.block_until_ready` barrier: retiring the ticket forces only
        this batch's outputs (np.asarray), never the whole device queue."""
        return self.execute_batch_async(reqs, rng=rng).retire()

    def sync(self) -> None:
        """Retire every in-flight ticket — the telemetry-flush barrier of
        the pipelined paths."""
        while self._inflight:
            self._inflight[0].retire()

    # -- standalone fixed-batch streaming mode ------------------------------

    def run(self, requests: Iterable[Dict[str, np.ndarray]],
            pipeline: bool = True) -> ServeStats:
        """Stream ``requests`` through fixed-size batches.

        ``pipeline=True`` (default): batch k+1 is staged into a free
        arena slot and dispatched while batch k's async dispatch is still
        computing; tickets retire lazily when the slot pool runs dry and
        once at stream end (the telemetry flush). ``overlapped`` is the
        MEASURED saving: serial phase sum minus end-to-end wall time.

        ``pipeline=False``: strictly serial stage -> compute -> readback
        per batch (each ticket retires before the next dispatch)."""
        reqs = list(requests)
        phases = PhaseTimes()
        if not reqs:                        # empty stream: zero-request stats
            return ServeStats(n_requests=0, n_kept=0, phases=phases, fps=0.0)
        kept = 0
        rng = jax.random.PRNGKey(0)
        batches = [reqs[i:i + self.batch_size]
                   for i in range(0, len(reqs), self.batch_size)]

        tickets: Deque[DispatchTicket] = deque()

        def _retire_next() -> None:
            nonlocal kept
            res = tickets.popleft().retire()
            kept += sum(res.keep)
            phases.stage_in += res.stage_time
            phases.compute += res.compute_time
            phases.stage_out += res.output_time

        wall0 = time.perf_counter()
        for chunk in batches:
            if pipeline:
                # lazy retirement: only when the pool would starve
                while tickets and self.arena.n_free == 0:
                    _retire_next()
            t0 = time.perf_counter()
            staged, slot, rows = self._stage(chunk)
            stage_t = time.perf_counter() - t0
            ticket, rng = self._issue(staged, slot, rows, len(chunk),
                                      stage_t, rng)
            tickets.append(ticket)
            if not pipeline:
                _retire_next()
        while tickets:                      # stream-end flush
            _retire_next()
        wall = time.perf_counter() - wall0

        phases.overlapped = max(phases.serial - wall, 0.0)
        fps = len(reqs) / max(wall, 1e-12)
        return ServeStats(n_requests=len(reqs), n_kept=kept, phases=phases,
                          fps=fps)
