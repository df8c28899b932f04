"""Staged cohort ingest pipeline (DESIGN.md §10).

One object owns the whole host->device feed path of the fused cohort
round as four explicit stages:

    read           source.client_batches(client, round)  (ingest/sources)
    decode/augment inside the source's iterable — disk sources decode
                   raw uint8 records and apply augmentation lazily as
                   the stacker consumes them (ingest/datasets)
    cohort-stack   stack_cohort_into a preallocated ring-slot buffer
                   (ingest/stack)
    device-place   jax.device_put against the round's actual sharding
                   (ingest/placement)

With ``depth >= 1`` staging buffers and ``CohortPrefetcher`` the stages
for round t+1..t+depth run on a producer thread while round t's program
runs on device. ``device_stage=True`` moves the device-place stage onto
the producer thread too: the H2D transfer overlaps compute, dispatch
finds every input already resident, and the consumer's only wait is the
staging wait (RoundRecord.ingest_host_seconds). ``device_stage=False``
keeps placement on the consumer thread, where it is measured as
RoundRecord.ingest_device_seconds — the historical "transfer at
dispatch" cost the two knobs exist to remove.

Each stage is a host span (repro.trace, DESIGN.md §16) carrying the
round it stages: ``fl.ingest.stage`` around the producer's whole work for
a round, with ``fl.ingest.sample``, ``fl.ingest.read`` (the gathers: the
read stage drains each client's batch iterator), ``fl.ingest.stack`` and
``fl.ingest.place`` inside it; ``fl.ingest.wait`` around the consumer's
wait on the ring, and ``fl.ingest.place`` on the consumer's thread where
placement runs there.

INDEX INGEST: where the round runs on one device (no input sharding,
no ``local_rows``) and the source offers the index form
(``sample_table`` / ``client_selections``, ingest/sources.py), the
first staged round places the source's table on the device, without
waiting for the copy, which overlaps the round's first compile. Each
round then reads and stacks the clients' (B,) sample indices instead of
their pixels — the same padding rules — places the small (K, M, B)
int32 stack, and hands the round an ``IndexedBatches``
(ingest/stack.py), whose batches the round program gathers on the
device. The batch values are the pixel path's, bit for bit.

Client SAMPLING stays inside ``sample_fn`` (the trainer's, which
snapshots pre-draw RNG/sampler state for checkpointing): the pipeline
calls it in round order from whichever thread stages the round, so a
prefetched run draws the exact same schedule as a blocking one.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np

from repro.ingest.placement import CohortPlacer
from repro.ingest.prefetch import CohortPrefetcher
from repro.ingest.sources import DataSource
from repro.ingest.stack import IndexedBatches, stack_cohort_into
from repro.trace import span, timed

PyTree = Any


@dataclass
class StagedCohort:
    """One round's fully staged inputs + the consumer-side waits that
    produced them. ``batches``/``masks``/``ids`` are committed device
    values ready for zero-copy dispatch; ``clients`` is the UNPADDED
    sampled cohort (host), for loss masking and the schedule. The
    seconds are the durations of the round's ingest spans, and
    ``record_fields()`` hands them, with the counters, to RoundRecord.

    ``release()`` returns the staging buffer to the ring and MUST be
    called (idempotent; a try/finally around the round's dispatch+sync)
    only after the round has synchronized on its results — a leaked slot
    deadlocks a later ``get``, an early release lets the producer
    overwrite buffers the device may still read."""
    round: int
    clients: np.ndarray
    batches: PyTree
    masks: Any
    ids: Any
    host_seconds: float = 0.0      # blocked on sample+read+stack staging
    device_seconds: float = 0.0    # blocked on H2D placement at dispatch
    stage_seconds: float = 0.0     # fl.ingest.stage, on the staging thread
    place_seconds: float = 0.0     # fl.ingest.place, on whichever thread
    ready: int = 0                 # staged rounds waiting when asked for
    steps_useful: int = 0          # unmasked (client, step) slots
    steps_computed: int = 0        # all slots of the staged mask
    steps_run: int = 0             # client-steps the round computes
    placed_bytes: int = 0          # bytes this round places on device
    _release: Optional[Callable[[], None]] = field(default=None, repr=False)

    def record_fields(self) -> dict:
        """The RoundRecord fields this round's ingest fills."""
        return {"ingest_host_seconds": self.host_seconds,
                "ingest_device_seconds": self.device_seconds,
                "ingest_stage_seconds": self.stage_seconds,
                "ingest_place_seconds": self.place_seconds,
                "ingest_ready": self.ready,
                "steps_useful": self.steps_useful,
                "steps_computed": self.steps_computed,
                "steps_run": self.steps_run,
                "ingest_placed_bytes": self.placed_bytes}

    def release(self):
        if self._release is not None:
            release, self._release = self._release, None
            release()


class CohortIngestPipeline:
    """Stages cohorts for the vectorized round; also owns the read stage
    (and the grow-once M shape bucket) for the serial reference path.

    ``sample_fn(t) -> (K,) client ids`` is called exactly once per round
    in round order. ``pad_to`` > K appends masked dummy clients so the
    cohort tiles a sharded client axis; dummy ids use the out-of-range
    ``num_clients`` sentinel (FedVARP's scatter drops them).

    MULTI-PROCESS (DESIGN.md §15): ``local_rows=(lo, hi)`` restricts the
    read/decode/stack stages to this host's slice of the padded cohort.
    Every process still draws the FULL schedule (seeded samplers make it
    identical across hosts — that shared draw IS the coordination), but
    only global rows [lo, hi) are read and staged here; the placer (which
    must carry the same ``local_rows``) assembles the global array from
    the per-host shards, so client batches never cross a host boundary
    host-side. ``sync_max_batches(tag, m) -> m'`` (launch/distributed.
    kv_allmax under a per-round tag) keeps the grow-once M bucket — and
    with it the stacked shape and jit signature — agreed across hosts.

    ``steps_run(mask) -> int`` counts the client-steps the round program
    computes for a staged (K, M) mask (default: every slot).
    """

    def __init__(self, source: DataSource,
                 sample_fn: Callable[[int], np.ndarray], *,
                 num_clients: int, rounds: Optional[int], depth: int = 2,
                 device_stage: bool = True,
                 placer: Optional[CohortPlacer] = None,
                 pad_to: Optional[int] = None,
                 local_rows: Optional[tuple] = None,
                 sync_max_batches: Optional[Callable[[str, int], int]] = None,
                 stall_timeout: Optional[float] = None,
                 max_restarts: int = 0, restart_backoff: float = 0.05,
                 crash_hook: Optional[Callable[[int, int], bool]] = None,
                 steps_run: Optional[Callable[[np.ndarray], int]] = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.source = source
        self.sample_fn = sample_fn
        self.num_clients = num_clients
        # rounds=None -> open horizon (buffered-async waves: the number
        # of dispatches is dynamic); the ring backpressures on `depth`
        self.rounds = rounds
        self.depth = depth
        self.device_stage = device_stage
        self.placer = placer if placer is not None else CohortPlacer()
        self.pad_to = pad_to
        self.local_rows = local_rows
        self.sync_max_batches = sync_max_batches
        self._sync_calls: dict = {}     # round -> sync tags issued so far
        if local_rows is not None and pad_to is None:
            raise ValueError("local_rows staging needs pad_to (the global "
                             "padded cohort size)")
        self.stall_timeout = stall_timeout
        # producer supervision (DESIGN.md §12): a produce raise is
        # retried up to max_restarts times (lifetime budget) with
        # exponential backoff; crash_hook(t, attempt) -> bool is the
        # fault-injection seam (core/faults.FaultPlan.ingest_crash)
        self.max_restarts = max(0, int(max_restarts))
        self.restart_backoff = float(restart_backoff)
        self._crash_hook = crash_hook
        self._attempts: dict = {}       # round -> produce attempts so far
        self._sampled: dict = {}        # round -> drawn cohort (retry cache)
        self._blocking_restarts = 0     # stage_blocking's share of the tally
        self._blocking_rounds: dict = {}  # round -> stage_blocking restarts
        self._max_batches: Optional[int] = None
        self._ring: Optional[CohortPrefetcher] = None
        self._blocking_slot: dict = {}   # stage_blocking's private buffer
        self._steps_run = steps_run or (lambda mask: int(mask.size))
        # the index form's table (module docstring), and its device copy
        # once the first staged round has placed it
        self._host_table = (source.sample_table() if local_rows is None
                            and self.placer.input_sharding is None
                            else None)
        self._table = None

    # ---- shared read stage / shape bucket ----

    @property
    def max_batches(self) -> Optional[int]:
        """Grow-once M shape bucket (checkpointed as TrainerState.
        max_batches; the trainer's restore() writes it back)."""
        return self._max_batches

    @max_batches.setter
    def max_batches(self, value: Optional[int]):
        self._max_batches = value

    @property
    def indexed(self) -> bool:
        """True where rounds are staged as sample indices."""
        return self._host_table is not None

    def client_lists(self, clients: Sequence[int], t: int,
                     indices: bool = False):
        """READ (+decode) stage: materialize each sampled client's
        batches for round t — or, with ``indices``, each batch's sample
        indices — and grow the M bucket to the cohort max."""
        read = (self.source.client_selections if indices
                else self.source.client_batches)
        with span("fl.ingest.read", round=t):
            per_client = [list(read(int(c), t)) for c in clients]
        mx = max(len(b) for b in per_client)
        if self._max_batches is None or mx > self._max_batches:
            self._max_batches = mx      # grow-once; keeps jit cache small
        return per_client

    # ---- staging ----

    def _pad_ids(self, clients: np.ndarray) -> np.ndarray:
        ids = np.asarray(clients, np.int32)
        if self.pad_to is not None and self.pad_to > ids.shape[0]:
            # out-of-range sentinel ids: FedVARP's scatter DROPS them
            ids = np.concatenate(
                [ids, np.full(self.pad_to - ids.shape[0],
                              self.num_clients, np.int32)])
        return ids

    def _stage_host(self, t: int, slot: dict) -> StagedCohort:
        """sample -> read -> stack into the slot's buffers.

        The drawn cohort is cached per round until staging SUCCEEDS: a
        supervised producer retry must not call ``sample_fn`` again —
        the trainer's sampler snapshots pre-draw RNG state per round,
        and a re-draw would shift every later round's schedule."""
        if t in self._sampled:
            clients = self._sampled[t]
        else:
            with span("fl.ingest.sample", round=t):
                clients = self.sample_fn(t)
            self._sampled[t] = clients
        if self.local_rows is not None:
            # multi-process: the full schedule was drawn (identically on
            # every host), but READ only this host's slice of it
            lo, hi = self.local_rows
            k = len(clients)
            local = np.asarray(clients)[lo:min(hi, k)]
            if local.size == 0:
                raise ValueError(
                    f"host rows [{lo}, {hi}) hold no real clients of the "
                    f"{k}-client cohort — every host needs at least one; "
                    "use fewer processes or a larger cohort")
            lists = self.client_lists(local, t, self.indexed)
            if self.sync_max_batches is not None:
                n = self._sync_calls.get(t, 0)
                self._sync_calls[t] = n + 1
                self._max_batches = int(self.sync_max_batches(
                    f"{t}.{n}", int(self._max_batches)))
            pad_to, ids = hi - lo, self._pad_ids(clients)[lo:hi]
        else:
            lists = self.client_lists(clients, t, self.indexed)
            pad_to, ids = self.pad_to, self._pad_ids(clients)
        with span("fl.ingest.stack", round=t):
            batches, masks = stack_cohort_into(lists, self._max_batches,
                                               slot, pad_to=pad_to)
        # this process's rows of the staged mask, counted on the host
        return StagedCohort(t, clients, batches, masks, ids,
                            steps_useful=int(masks.sum()),
                            steps_computed=int(masks.size),
                            steps_run=self._steps_run(masks))

    def _place(self, staged: StagedCohort) -> float:
        """DEVICE-PLACE stage, in place; returns its seconds."""
        staged.placed_bytes = int(sum(
            np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(
                (staged.batches, staged.masks, staged.ids))))
        with timed("fl.ingest.place", round=staged.round) as place:
            staged.batches, staged.masks, staged.ids = self.placer.place(
                staged.batches, staged.masks, staged.ids)
        if self.indexed:
            if self._table is None:
                # not waited for: the first round's compile overlaps it
                self._table = jax.device_put(self._host_table)
            staged.batches = IndexedBatches(self._table, staged.batches)
        staged.place_seconds = place.seconds
        return place.seconds

    def _produce(self, t: int, slot: dict):
        """Ring-producer body. In device-staged mode the place stage
        runs here too, so the H2D wait lands on this thread (overlapped
        with device compute) instead of at dispatch. The crash hook
        fires BEFORE sampling so an injected crash + restart replays
        the exact no-fault RNG stream."""
        attempt = self._attempts.get(t, 0)
        self._attempts[t] = attempt + 1
        if self._crash_hook is not None and self._crash_hook(t, attempt):
            raise RuntimeError(
                f"injected ingest producer crash at round {t} "
                f"(attempt {attempt})")
        with timed("fl.ingest.stage", round=t) as stage:
            staged = self._stage_host(t, slot)
            if self.device_stage:
                self._place(staged)
        staged.stage_seconds = stage.seconds
        self._sampled.pop(t, None)
        self._attempts.pop(t, None)
        return staged

    def get(self, t: int) -> StagedCohort:
        """Prefetching consumer: blocks only until round t is staged
        (and, host-staged, on its own placement). Rounds must be
        consumed sequentially from the first ``get``; the caller owns
        the returned slot until ``StagedCohort.release()``."""
        if self._ring is None:
            self._ring = CohortPrefetcher(self._produce, t, self.rounds,
                                          slots=self.depth,
                                          stall_timeout=self.stall_timeout,
                                          max_restarts=self.max_restarts,
                                          restart_backoff=self.restart_backoff)
        ready = self._ring.pending()
        with timed("fl.ingest.wait", round=t) as wait:
            staged, slot = self._ring.get(t)
        staged.host_seconds, staged.ready = wait.seconds, ready
        if not self.device_stage:
            try:
                staged.device_seconds = self._place(staged)
            except BaseException:
                # failed placement cannot leak the slot — that would
                # deadlock the NEXT get() instead of erroring
                self._ring.release(slot)
                raise
        staged._release = lambda: self._ring.release(slot)
        return staged

    def stage_blocking(self, t: int) -> StagedCohort:
        """Non-prefetching path: stage round t inline on the caller's
        thread (out-of-order rounds allowed). Reuses one private slot —
        valid because the caller synchronizes each round before staging
        the next (release() is a no-op here)."""
        with timed("fl.ingest.stage", round=t) as stage:
            while True:
                try:
                    attempt = self._attempts.get(t, 0)
                    self._attempts[t] = attempt + 1
                    if (self._crash_hook is not None
                            and self._crash_hook(t, attempt)):
                        raise RuntimeError(
                            f"injected ingest producer crash at round {t} "
                            f"(attempt {attempt})")
                    staged = self._stage_host(t, self._blocking_slot)
                    break
                except BaseException:
                    if self._blocking_restarts >= self.max_restarts:
                        raise
                    self._blocking_restarts += 1
                    self._blocking_rounds[t] = (
                        self._blocking_rounds.get(t, 0) + 1)
                    if self.restart_backoff > 0:
                        time.sleep(self.restart_backoff * (2 ** attempt))
        self._sampled.pop(t, None)
        self._attempts.pop(t, None)
        staged.host_seconds = staged.stage_seconds = stage.seconds
        staged.device_seconds = self._place(staged)
        return staged

    # ---- lifecycle ----

    @property
    def started(self) -> bool:
        """True once the staging ring exists (some round was prefetched)."""
        return self._ring is not None

    @property
    def restart_count(self) -> int:
        """Total supervised producer recoveries so far (both the
        prefetch ring's and stage_blocking's), RoundRecord's
        ``ingest_restarts`` source."""
        ring = self._ring.restart_count if self._ring is not None else 0
        return ring + self._blocking_restarts

    def restarts_for(self, t: int) -> int:
        """Supervised recoveries attributed to round ``t``'s STAGING —
        keyed by the round index carried into produce_fn, not by
        whichever round was computing when the crash fired. Final once
        round t has been staged (the retry loop resolved before the
        staged item was handed out), which is exactly when the trainer
        reads it to fill RoundRecord.ingest_restarts."""
        ring = (self._ring.restart_rounds.get(t, 0)
                if self._ring is not None else 0)
        return ring + self._blocking_rounds.get(t, 0)

    def close(self):
        """Stop the staging ring. The source is CALLER-owned (sweeps
        share one across trainers) and is never closed here."""
        if self._ring is not None:
            self._ring.stop()
        self._table = None
