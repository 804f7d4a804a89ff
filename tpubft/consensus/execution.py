"""Execution lane — committed-slot execution off the dispatcher thread.

The reference separates ordering from post-execution (concord-bft's
post-execution separation + block accumulation: PostExecJob queues and
the accumulated-block commit in kv_blockchain): the dispatcher thread
marks slots committed and hands them over; a single executor thread
drains *runs* of consecutive committed slots in seqnum order and applies
each run as ONE coalesced commit:

  * one ledger commit per run — the handler's add_block calls stage into
    a shared WriteBatch via KeyValueBlockchain.begin/end_accumulation
    (read-your-writes overlay, PR 2's _StagedReadView), so N blocks cost
    one DB write instead of N;
  * one reserved-pages batch per run for the reply ring / at-most-once
    markers (folded into the ledger batch when pages share its DB —
    apply is then atomic across ledger and reply state);
  * replies are handed back to the dispatcher, whose send loop already
    rides the transport batcher.

Safety rules enforced here and in the replica wiring:

  * `last_executed` advances on the DISPATCHER, only after the run's
    durable apply (the completed-run handoff) — a crash between commit
    and apply replays the committed suffix, deduplicated by the
    reserved-pages at-most-once state;
  * runs never cross a checkpoint-window boundary, and the boundary
    run's state/pages digests are snapshotted HERE, before the next run
    can mutate state — checkpoint certificates stay comparable
    cluster-wide;
  * batches carrying INTERNAL/RECONFIG requests never reach the lane:
    the dispatcher drains it and executes them inline (they mutate
    dispatcher-owned subsystems: key exchange, cron, wedge control);
  * view change, wedge announcement, and state-transfer completion all
    drain the lane first (Replica._drain_exec_lane).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from tpubft.storage.interfaces import WriteBatch
from tpubft.testing.crashpoints import crashpoint
from tpubft.utils import flight
from tpubft.utils.logging import get_logger, mdc_scope
from tpubft.utils.racecheck import get_watchdog, make_lock

log = get_logger("execlane")


@dataclass
class CompletedRun:
    """A durably-applied run, ready for the dispatcher to integrate."""
    first: int
    last: int
    n_requests: int                       # executed (non-dedup) requests
    replies: List[Tuple[int, bytes]] = field(default_factory=list)
    reply_keys: List[Tuple[int, int]] = field(default_factory=list)
    # optimistic-reply mode with the durability pipeline: replies built
    # UNSIGNED during execution; the io thread signs the whole sealed
    # group in one batched sign at the group boundary and appends the
    # packed wire bytes to `replies` before the group burst
    unsigned: List[Tuple[int, object]] = field(default_factory=list)
    # set by the durability pipeline when it already pushed `replies`
    # as part of the group-boundary send burst — the dispatcher's
    # integration pass must not send them a second time
    replies_sent: bool = False
    # (seq, state_digest, pages_digest, block_id) when `last` is a
    # checkpoint boundary — snapshotted at the boundary, before the
    # next run ran. block_id is the ledger height the state digest
    # binds (None for non-ledger handlers) — the thin-replica anchor
    # needs it to resolve a certified digest to a block row.
    checkpoint: Optional[Tuple[int, bytes, bytes, Optional[int]]] = None


class ExecutionLane:
    """Single executor thread + the dispatcher↔executor handoff.

    Dispatcher-side API: submit / drain / pop_completed / depth. All
    protocol state stays dispatcher-owned; the lane touches only
    thread-safe surfaces (handler execution, ClientsManager, reserved
    pages, the blockchain's accumulation bracket)."""

    RETRY_DELAY_S = 0.5                   # backoff after a failed run

    def __init__(self, replica, max_accumulation: int,
                 checkpoint_window: int) -> None:
        self._r = replica
        self._max_acc = max(1, max_accumulation)
        self._ckpt_window = checkpoint_window
        self._mu = make_lock("exec_lane")
        self._cond = threading.Condition(self._mu)
        # committed slots, (seq, pre_prepare), consecutive
        self._pending: "deque[Tuple[int, object]]" = deque()
        self._completed: "deque[CompletedRun]" = deque()
        self._busy = False
        self._held = False                # test hook: freeze execution
        self._retry_at = 0.0
        # durability-pipeline dedup bridge: (client, req_seq) -> reply
        # for requests executed in SEALED runs whose group fsync has
        # not landed yet. The at-most-once ClientsManager state only
        # becomes visible post-fsync (a retransmit must never be
        # answered from a run that could still be lost), but the LANE
        # must still dedup across back-to-back runs — the same request
        # re-proposed into a later slot (view change after an
        # equivocation, primary retry) would otherwise execute twice
        # before the first run's group lands: duplicate block,
        # permanent divergence. Written by the lane thread at seal,
        # erased by the io thread at completion (strictly AFTER
        # on_request_executed makes the ClientsManager entry visible,
        # so there is no uncovered window).
        self._inflight: Dict[Tuple[int, int], object] = {}
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._name = f"exec-{replica.id}"

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=self._name)
        self._thread.start()

    def stop(self) -> None:
        """Stop WITHOUT draining: pending slots are committed state that
        recovery replays — stop is crash-equivalent by design."""
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        get_watchdog().unregister(self._name)

    def set_max_accumulation(self, n: int) -> None:
        """Autotuner actuator: retune the run-coalescing cap live. The
        lane thread reads it once per run pop (under the condition), so
        the new cap applies from the next run."""
        with self._cond:
            self._max_acc = max(1, int(n))

    @property
    def max_accumulation(self) -> int:
        return self._max_acc

    # ------------------------------------------------------------------
    # dispatcher-side API
    # ------------------------------------------------------------------
    def submit(self, seq: int, pre_prepare) -> None:
        """Hand a committed slot to the lane. The dispatcher submits in
        strictly increasing consecutive seq order."""
        with self._cond:
            if self._pending and seq != self._pending[-1][0] + 1:
                raise RuntimeError(
                    f"non-consecutive lane submit: {seq} after "
                    f"{self._pending[-1][0]}")
            self._pending.append((seq, pre_prepare))
            self._cond.notify_all()
        self._r.m_exec_lane_depth.set(self.depth)

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every submitted slot has been applied (pending
        empty, no run in flight). Returns False on timeout — the caller
        decides whether proceeding is safe. The executor never waits on
        the dispatcher, so this cannot deadlock."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._pending or self._busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(min(remaining, 0.2))
        return True

    def complete_durable(self, run: CompletedRun) -> None:
        """Durability-pipeline completion hop (io thread): the run's
        group fsync landed — only now does it reach the dispatcher's
        integration queue (replies, `last_executed`, checkpoint votes).
        The caller (the pipeline) made the ClientsManager at-most-once
        entries visible FIRST, so dropping the in-flight dedup entries
        here leaves no uncovered window."""
        with self._cond:
            for key in run.reply_keys:
                self._inflight.pop(key, None)
            self._completed.append(run)
            self._cond.notify_all()

    def pop_completed(self) -> List[CompletedRun]:
        out = []
        with self._cond:
            while self._completed:
                out.append(self._completed.popleft())
        return out

    @property
    def depth(self) -> int:
        return len(self._pending)

    def idle(self) -> bool:
        with self._cond:
            return not self._pending and not self._busy

    # test hooks: freeze/unfreeze the lane so crash-window tests can
    # create "committed persisted, not yet applied" states determinately
    def hold(self) -> None:
        with self._cond:
            self._held = True

    def release(self) -> None:
        with self._cond:
            self._held = False
            self._cond.notify_all()

    # ------------------------------------------------------------------
    # executor thread
    # ------------------------------------------------------------------
    def _loop(self) -> None:
        watchdog = get_watchdog()
        # health-probe semantics are PROGRESS, not thread liveness: the
        # beat fires when the lane is idle (fresh age when work arrives)
        # and after each durable apply — depth > 0 with no apply for
        # execution_drain_timeout_ms reads as a stall (a wedged handler,
        # a run stuck behind a dead DB, or a held lane), even while this
        # thread is alive and waiting.
        health = getattr(self._r, "health", None)
        flight.set_thread_rid(self._r.id)
        with mdc_scope(r=self._r.id):
            while True:
                watchdog.beat(self._name)
                with self._cond:
                    while self._running and (
                            self._held or not self._pending
                            or time.monotonic() < self._retry_at):
                        if health is not None and not self._pending:
                            health.beat("exec_lane")
                        self._cond.wait(0.2)
                        watchdog.beat(self._name)
                    if not self._running:
                        return
                    run = self._take_run_locked()
                    self._busy = True
                # ---- outside the condition ----
                try:
                    # the lane's run on both clocks: execute + the
                    # coalesced apply
                    with flight.span("exec_run", run[0][0]):
                        self._execute_run(run)
                    if health is not None:
                        health.beat("exec_lane")      # durable apply
                except Exception:  # noqa: BLE001 — retry the run
                    log.exception("run [%d..%d] failed; will retry",
                                  run[0][0], run[-1][0])
                    with self._cond:
                        self._pending.extendleft(reversed(run))
                        self._retry_at = (time.monotonic()
                                          + self.RETRY_DELAY_S)
                finally:
                    with self._cond:
                        self._busy = False
                        self._cond.notify_all()
                self._r.m_exec_lane_depth.set(self.depth)

    def _take_run_locked(self) -> List[Tuple[int, object]]:
        """Pop the next run: consecutive pending slots, capped at
        execution_max_accumulation, always breaking AFTER a checkpoint
        boundary so digests are computed at cluster-agreed points."""
        run: List[Tuple[int, object]] = []
        while self._pending and len(run) < self._max_acc:
            seq = self._pending[0][0]
            if run and seq != run[-1][0] + 1:
                break                      # gap
            run.append(self._pending.popleft())
            if seq % self._ckpt_window == 0:
                break
        return run

    # ------------------------------------------------------------------
    # run execution
    # ------------------------------------------------------------------
    def _execute_run(self, run: List[Tuple[int, object]]) -> None:
        r = self._r
        blockchain = getattr(r.handler, "blockchain", None)
        can_accumulate = (blockchain is not None
                          and hasattr(blockchain, "begin_accumulation"))
        pages_wb = WriteBatch()
        result = CompletedRun(first=run[0][0], last=run[-1][0],
                              n_requests=0)
        # ClientsManager updates deferred to AFTER the durable commit:
        # an aborted run retries, and the at-most-once state must not
        # claim requests whose staged effects were discarded. _run_seen
        # is the run-local dedup (a byzantine primary re-batching one
        # request into two of the run's slots).
        executed_now: List[Tuple[int, int, object]] = []
        self._run_seen = set()
        acc = False
        if can_accumulate:
            blockchain.begin_accumulation()
            acc = True
        try:
            for seq, pp in run:
                flight.record(flight.EV_EXEC_START, seq=seq, arg=len(run))
                with flight.annotate("exec_slot"):
                    app_us = self._execute_slot(seq, pp, pages_wb, result,
                                                executed_now)
                flight.record(flight.EV_EXEC_HANDLED, seq=seq, arg=app_us)
        except BaseException:
            if acc:
                blockchain.abort_accumulation()
            raise
        self._apply_run(len(run), result, pages_wb, executed_now,
                        blockchain, acc)

    def _apply_run(self, run_len: int, result: CompletedRun,
                   pages_wb: WriteBatch, executed_now, blockchain,
                   acc: bool) -> None:
        """Coalesced apply: ONE ledger commit + ONE pages batch per run
        (a single atomic batch when they share a DB). Everything up to
        and including the LEDGER commit point is retriable
        (end_accumulation rolls the head back on failure); everything
        AFTER it is the point of no return — a post-commit exception
        must never requeue the run, or the retry would re-execute
        requests whose blocks are already committed (duplicate blocks,
        permanent state divergence).

        The run's batch is SEALED, not written: the overlay moves into
        the durability pipeline's pending store (still readable by
        every thread), the io thread group-commits it across runs with
        one fsync per group, and only then do replies, `last_executed`
        and the at-most-once cache advance — this thread never touches
        the disk and moves straight to the next run. A ledger without
        the pending overlay (no accumulation bracket, or reply pages in
        a store of their own) is written here and rides the group for
        its fsync alone."""
        r = self._r
        crashpoint("exec.pre_apply", rid=r.id)
        folded = False
        deferred = None                   # (run_no, batch, raw base db)
        # the slot's `exec_seal` on the profiler's clock; its ring half
        # ends at the EV_EXEC_APPLY events below
        with flight.annotate("exec_seal"):
            if acc:
                folded = (pages_wb.ops
                          and r.res_pages.shares_db(
                              getattr(blockchain, "_base_db", None)))
                # deferral requires the WHOLE run to ride one deferred
                # batch: with reply pages in a SEPARATE store (not
                # folded) the pages write would land at seal while the
                # ledger batch waited in memory — a crash in that window
                # persists "request executed" without its block, and
                # replay would skip it forever. Fall back to the
                # immediate apply there (ledger first, pages second,
                # same thread); the seal below still groups the fsyncs.
                defer = (getattr(blockchain, "durability_attached", False)
                         and (folded or not pages_wb.ops))
                blockchain.end_accumulation(
                    extra=pages_wb if folded else None, defer=defer)
                if defer:
                    deferred = blockchain.take_deferred()
            if not folded:
                # without accumulation the handler's effects applied
                # irreversibly during execution, and with it the ledger
                # just committed — either way a pages failure here is
                # logged, never retried (in-memory at-most-once still
                # dedups; the at-risk window is a crash before the next
                # run persists the ring)
                try:
                    r.res_pages.write_batch(pages_wb)
                except Exception:  # noqa: BLE001
                    log.exception("run [%d..%d]: reply-pages batch "
                                  "failed post point-of-no-return",
                                  result.first, result.last)
        try:
            crashpoint("exec.post_apply", rid=r.id)
            # durable-apply flight events, one per slot (the `exec`
            # stage's end anchor; `reply` runs from here to the
            # dispatcher's integration)
            for seq in range(result.first, result.last + 1):
                flight.record(flight.EV_EXEC_APPLY, seq=seq, arg=run_len)
            # checkpoint-boundary snapshot: digests taken now, before
            # the next run mutates state
            if result.last % self._ckpt_window == 0:
                try:
                    state_digest = r.handler.state_digest()
                    # ledger height snapshotted WITH the digest (same
                    # thread, same boundary): resolves the certified
                    # digest to a block for the thin-replica anchor
                    head = getattr(blockchain, "last_block_id", None)
                    if r.state_transfer is not None:
                        r.state_transfer.on_checkpoint_created(
                            result.last, state_digest)
                    result.checkpoint = (result.last, state_digest,
                                         r.res_pages.digest(), head)
                except Exception:  # noqa: BLE001 — skip OUR checkpoint
                    # vote for this boundary; peers' quorum can still
                    # certify it, and re-executing the run would be
                    # strictly worse (duplicate blocks)
                    log.exception("checkpoint snapshot failed at %d",
                                  result.last)
            r.record_exec_run(run_len)
        except Exception:  # noqa: BLE001 — the run is durable: a
            # post-commit bookkeeping failure must be SWALLOWED, never
            # reach _loop's requeue path (re-executing a committed run
            # appends duplicate blocks — permanent divergence)
            log.exception("post-commit bookkeeping failed for run "
                          "[%d..%d] (run still completes)",
                          result.first, result.last)
        finally:
            # the run IS committed no matter what the post-commit
            # bookkeeping did — hand it to the durability pipeline:
            # completion (the at-most-once records, replies, the
            # dispatcher's integration) follows its group fsync
            from tpubft.durability import SealedRun
            from tpubft.kvbc.blockchain import raw_base
            sync_dbs = []
            if deferred is None and blockchain is not None:
                # nothing deferred (empty batch, or a ledger without
                # the accumulation bracket whose writes applied
                # directly): the base still holds unsynced buffers the
                # group fsync must land
                db = raw_base(getattr(blockchain, "_db", None))
                if db is not None:
                    sync_dbs.append(db)
            if not folded and pages_wb.ops:
                pdb = raw_base(r.res_pages.db)
                if not any(pdb is d for d in sync_dbs):
                    sync_dbs.append(pdb)
            run_no, batch, target = (deferred if deferred is not None
                                     else (None, None, None))
            # publish the in-flight dedup entries BEFORE the seal: from
            # the moment the pipeline owns the run, the next run may
            # execute — it must already see these
            with self._cond:
                for client, req_seq, reply in executed_now:
                    self._inflight[(client, req_seq)] = reply
            r.durability.seal(SealedRun(
                run=result, executed_now=list(executed_now),
                batch=batch, run_no=run_no, db=target,
                sync_dbs=tuple(sync_dbs)))

    def _execute_slot(self, seq: int, pp, pages_wb: WriteBatch,
                      result: CompletedRun,
                      executed_now: List[Tuple[int, int, object]]) -> int:
        """One slot's requests, in order; returns the µs its application
        calls took, summed (the slot's `exec_app`). Only plain /
        pre-processed client requests reach the lane (barrier batches
        run inline on the dispatcher)."""
        r = self._r
        seen = self._run_seen
        app_ns = 0
        # batched reply signing (optimistic replies): per-reply scalar
        # signs during execution serialize ~100µs of comb math behind
        # every request — defer them to the io thread, which signs the
        # sealed GROUP in one batch at its fsync boundary (the reply
        # cannot leave before that boundary anyway, so the deferral adds
        # zero client-visible latency)
        defer = getattr(r, "_opt_replies", False)
        for req in pp.client_requests():
            client = req.sender_id
            key = (client, req.req_seq_num)
            # sealed-but-not-durable dedup (pipeline mode): the request
            # already executed in a run awaiting its group fsync — the
            # ClientsManager entry is deliberately not visible yet, but
            # executing again would append a duplicate block. Re-issue
            # the stashed reply with THIS run (it rides this run's own
            # durability gate). READ ORDER MATTERS: the io thread
            # publishes the ClientsManager entry BEFORE popping the
            # in-flight entry, so checking _inflight FIRST and the
            # manager second can never observe the uncovered
            # none-visible-yet window (checking the manager first
            # could: miss there, completion lands, miss here too).
            # GIL-atomic read; see _inflight.
            stashed = self._inflight.get(key)
            if stashed is not None:
                if defer and not stashed.signature:
                    # the stashed reply's own group has not signed it
                    # yet — route the re-issue through THIS run's batch
                    # sign instead of packing unsigned bytes (ed25519
                    # signing is deterministic, so a double sign from
                    # both groups lands identical bytes)
                    result.unsigned.append((client, stashed))
                else:
                    result.replies.append((client, stashed.pack()))
                continue
            if key in seen or r.clients.was_executed(client,
                                                     req.req_seq_num):
                cached = r.clients.cached_reply(client, req.req_seq_num)
                if cached is not None:
                    result.replies.append((client, cached.pack()))
                continue
            if r._slowdown.enabled:
                from tpubft.testing.slowdown import PHASE_EXECUTE
                r._slowdown.delay(PHASE_EXECUTE)
            t0 = time.perf_counter_ns()
            payload = r._execute_request(req, seq)
            app_ns += time.perf_counter_ns() - t0
            result.n_requests += 1
            reply, wire = r._build_reply(client, req.req_seq_num,
                                         payload, pages_wb,
                                         defer_sign=defer)
            executed_now.append((client, req.req_seq_num, reply))
            seen.add(key)
            result.reply_keys.append(key)
            if wire is not None:
                result.replies.append((client, wire))
            elif defer and not r.info.is_internal_client(client):
                result.unsigned.append((client, reply))
        if r.cfg.time_service_enabled and pp.time:
            # agreed-time page writes must stay seq-ordered with the
            # reply pages for checkpoint digest determinism
            r.time_service.on_executed(pp.time)
        return app_ns // 1000
