"""Notary actor: joins the pool, watches heads, votes on data availability.

Parity: `sharding/notary/service.go` (Start :31, notarizeCollations :44)
and `notary.go` (subscribeBlockHeaders :28, checkSMCForNotary :62,
joinNotaryPool :267, leaveNotaryPool :318, releaseNotary :365, submitVote
:413, verifyNotary :245, isLockUpOver :129). The vote path — which the
reference only exercises from tests — is wired into the head loop here:

  head -> in pool? -> per shard: sampled for committee? -> collation record
  exists for this period? -> chunk-root/availability check (requesting the
  body over shardp2p if missing) -> submitVote at our poolIndex -> on
  quorum, set the header canonical in the shardDB.

The `sig_backend` seam is where batched TPU verification plugs in: votes
for all shards in a period are verified as one batch (see
`gethsharding_tpu.ops` and BASELINE.md configs 2-3).
"""

from __future__ import annotations

import os
import time
from typing import Callable, List, Optional, Tuple

from gethsharding_tpu import metrics, tracing
from gethsharding_tpu.actors.base import Service
from gethsharding_tpu.core.shard import Shard, ShardError
from gethsharding_tpu.core.types import CollationHeader
from gethsharding_tpu.serving.classes import CLASS_BULK_AUDIT, admission_class
from gethsharding_tpu.mainchain.client import SMCClient
from gethsharding_tpu.p2p.messages import CollationBodyRequest
from gethsharding_tpu.p2p.service import P2PServer
from gethsharding_tpu.params import Config, DEFAULT_CONFIG
from gethsharding_tpu.resilience.errors import FetchAborted, TransientError
from gethsharding_tpu.resilience.policy import (POLL_MISS, RetryExecutor,
                                                RetryPolicy, poll_probe)
from gethsharding_tpu.sigbackend import SigBackend, get_backend
from gethsharding_tpu.smc.state_machine import SMCRevert, vote_digest


class _BodyUnavailable(TransientError):
    """A collation body did not arrive within one fetch attempt."""


class Notary(Service):
    name = "notary"
    supervisable = True

    def __init__(self, client: SMCClient, shard: Shard,
                 p2p: Optional[P2PServer] = None,
                 config: Config = DEFAULT_CONFIG,
                 deposit_flag: bool = False,
                 all_shards: bool = True,
                 sig_backend: Optional[SigBackend] = None,
                 mirror=None,
                 journal=None,
                 das=None,
                 da_mode: str = "full"):
        super().__init__()
        self.client = client
        self.shard = shard
        self.p2p = p2p
        # data-availability sampling (--da-mode=sampled + a DASService):
        # the availability verdict comes from k sampled chunk proofs
        # verified in ONE batched das_verify_samples dispatch across all
        # candidate shards — the notary never fetches a collation body
        self.das = das
        self.da_mode = da_mode
        # positive sampled verdicts are cached per (shard, period): a
        # collation's chunks are immutable content, so once k samples
        # verified, re-entering the head loop (or the windback walk)
        # must NOT re-fetch k chunks — the acceptance bound is
        # k·chunk_size + proof overhead PER COLLATION. Negative
        # verdicts are never cached (late-arriving samples may still
        # flip them). Bounded by pruning below _DA_CACHE_MAX.
        self._da_verdicts: dict = {}
        # crash-safe vote journal (resilience/journal.VoteJournal): a
        # restarted notary recovers its submitted (shard, period) votes
        # and the audit high-water mark on on_start, so it neither
        # double-votes nor re-audits finished periods. None = process
        # memory only (the pre-resilience behavior).
        self.journal = journal
        # eth/downloader analog (mainchain/mirror.StateMirror): when set,
        # the per-head phase-1 scan reads records/watermarks/committee
        # context from ONE bulk snapshot pull instead of O(shards) client
        # round trips — the difference between 1 and ~300 RPC calls per
        # head for a remote (--endpoint) notary
        self.mirror = mirror
        self.config = config
        self.deposit_flag = deposit_flag
        # notaries watch every shard (the reference scans 0..shardCount)
        self.all_shards = all_shards
        self.sig_backend = sig_backend or get_backend("python")
        self.votes_submitted = 0
        self.canonical_set = 0
        self.signatures_rejected = 0
        self.audits_run = 0
        self.audit_mismatches = 0
        self.aggregate_sigs_verified = 0
        self._last_audited_period = 0
        self._unsubscribe = None
        # the two BASELINE metrics (SURVEY.md §7.8): aggregate notary
        # signature verifications/sec and collation validate latency
        self.m_sigs_verified = metrics.counter(
            "notary/aggregate_sig_verifications")
        self.m_validate_latency = metrics.timer("notary/validate_latency")
        self.m_audit_latency = metrics.timer("notary/period_audit_latency")
        self.m_votes = metrics.counter("notary/votes_submitted")
        self.m_audit_mismatch = metrics.counter("notary/audit_mismatches")
        self.m_windback_checks = metrics.counter("notary/windback_checks")
        # body-fetch retry seam (resilience/policy): each attempt
        # re-broadcasts the shardp2p request and polls briefly — a lost
        # request frame costs one backoff, not the whole availability
        # verdict
        self._body_retry = RetryExecutor(
            "collation_body",
            RetryPolicy(attempts=3, base_s=0.05, cap_s=0.2,
                        retryable=(_BodyUnavailable,)))

    # -- lifecycle ---------------------------------------------------------

    def on_start(self) -> None:
        if self.journal is not None:
            # a journal AHEAD of the chain belongs to a previous chain
            # lifetime (wiped devnet, fresh simulated chain under an
            # old datadir): replaying it would mute the notary until
            # the new chain catches up to the stale watermark. An
            # unreachable chain keeps the journal — surviving exactly
            # that outage is what the journal is for.
            try:
                current = self.client.current_period()
            except Exception:  # noqa: BLE001 - chain down at boot
                current = None
            if current is not None \
                    and self.journal.invalidate_if_reset(current):
                self.log.warning(
                    "vote journal was ahead of the chain (period %d): "
                    "chain reset assumed, journal cleared", current)
            # recovery replay: a restart must not re-audit periods the
            # crashed instance already finished (the vote-side replay is
            # per (shard, period) in submit_vote). The journal records
            # the audited period itself (None = never audited);
            # `_last_audited_period = N` means "period N-1 audited",
            # hence the +1.
            high_water = self.journal.audit_high_water()
            if high_water is not None \
                    and high_water + 1 > self._last_audited_period:
                self._last_audited_period = high_water + 1
            recovered = sum(1 for _ in self.journal.votes())
            if recovered or high_water is not None:
                self.log.info(
                    "vote journal recovered: %d submitted votes, audit "
                    "high-water period %s", recovered, high_water)
        if self.deposit_flag:
            try:
                self.join_notary_pool()
            except Exception as exc:
                self.record_error(f"joining notary pool failed: {exc}")
        self._unsubscribe = self.client.subscribe_new_head(self._on_head)

    def on_stop(self) -> None:
        if self._unsubscribe is not None:
            self._unsubscribe()

    # -- pool membership (notary.go:267,318,365) ---------------------------

    def join_notary_pool(self) -> None:
        registry = self.client.notary_registry()
        if registry is not None and registry.deposited:
            self.log.info("Already joined notary pool")
            return
        self.client.register_notary()
        self.log.info("Joined notary pool: %s", self.client.account().hex_str)

    def leave_notary_pool(self) -> None:
        self.client.deregister_notary()

    def release_notary(self) -> None:
        registry = self.client.notary_registry()
        if registry is None or registry.deregistered_period == 0:
            raise RuntimeError("account has not deregistered")
        if not self.is_lockup_over(registry):
            raise RuntimeError("lockup period is not over")
        self.client.release_notary()

    def is_lockup_over(self, registry) -> bool:
        """isLockUpOver (notary.go:129)."""
        return (self.client.current_period()
                > registry.deregistered_period + self.config.notary_lockup_length)

    def is_account_in_notary_pool(self) -> bool:
        registry = self.client.notary_registry()
        return registry is not None and registry.deposited

    # -- the hot loop (notarizeCollations / checkSMCForNotary) -------------

    def _on_head(self, block) -> None:
        try:
            self.notarize_collations(head=block.number)
            self.record_success()
        except Exception as exc:
            # a run of consecutive head failures marks the service crashed
            # for the supervisor (callback actors have no loop to die)
            self.record_failure(
                f"notarize failed at head {block.number}: {exc}")

    def _head_snapshot(self, head: Optional[int]):
        """The mirror snapshot for this head, refreshed if the mirror has
        not caught up yet (ONE bulk pull); None = read via the client."""
        if self.mirror is None:
            return None
        if head is None:
            head = self.client.block_number
        try:
            snap = self.mirror.snapshot()
            if snap is None or (snap["block_number"] or 0) < head:
                snap = self.mirror.refresh()
        except Exception:
            return None  # degraded mirror: fall back to direct reads
        if snap is None or (snap["block_number"] or 0) < head:
            return None
        return snap

    def notarize_collations(self, head: Optional[int] = None) -> None:
        # the per-head trace root: fetch -> recover -> vote phases below
        # parent under it, and (with --serving) the recovery dispatch's
        # serving/... request spans stitch to the recover phase
        with tracing.span("notary/notarize"):
            self._notarize_collations(head)

    def _notarize_collations(self, head: Optional[int]) -> None:
        if not self.is_account_in_notary_pool():
            return
        snap = self._head_snapshot(head)
        if snap is not None:
            period = snap["period"]
            block_number = snap["block_number"]
            shard_count = snap["shard_count"]
        else:
            period = self.client.current_period()
            block_number = self.client.block_number
            shard_count = self.client.shard_count()
        # audit the previous period's aggregate votes once, in one batched
        # device dispatch (the re-architected hot loop; see audit_period).
        # With overlap on (GETHSHARDING_NOTARY_OVERLAP, default), the
        # dispatch is FIRED here and the verdict pulled only after the
        # vote phases: the device verifies period N-1 while this thread
        # fetches candidates, recovers proposer signatures and votes —
        # the host pull stays off the critical path until the verdict
        # is actually needed (the audit counters/mismatch report).
        finish_audit: Optional[Callable[[], None]] = None
        prev_audited = self._last_audited_period
        if period > 0 and self._last_audited_period < period:
            if self._overlap_enabled():
                finish_audit = self._begin_period_audit(period - 1)
            else:
                self.audit_period(period - 1)
            self._last_audited_period = period
        try:
            self._vote_phases(snap, period, block_number, shard_count)
        except Exception:
            # the vote-phase failure wins; still collect the audit
            # verdict (its device work is done — dropping the future
            # would silently skip the mismatch checks for this period)
            if finish_audit is not None:
                try:
                    finish_audit()
                except Exception as audit_exc:
                    # transient collect failure: rewind the watermark so
                    # the NEXT head retries this period's audit (the
                    # sync path's retry semantics)
                    self._last_audited_period = prev_audited
                    self.record_error(
                        f"period audit failed behind a vote-phase "
                        f"error: {audit_exc}")
            raise
        if finish_audit is not None:
            try:
                finish_audit()
            except Exception:
                self._last_audited_period = prev_audited  # retry next head
                raise

    def _vote_phases(self, snap, period: int, block_number: int,
                     shard_count: int) -> None:
        # a vote submitted now executes in the PENDING block; if that block
        # already belongs to the next period the SMC will revert with
        # "period is not current" — skip and wait for the new period's head
        pending_period = (block_number + 1) // self.config.period_length
        if pending_period != period:
            return
        shard_ids = (range(shard_count)
                     if self.all_shards else [self.shard.shard_id])

        # phase 1: collect every eligible (shard, record) pair this period
        # — from the snapshot (zero extra round trips) when mirrored
        candidates: List[Tuple[int, int, object]] = []
        with tracing.span("notary/fetch"):
            for shard_id in self._eligible_shards(shard_ids, snap):
                if snap is not None:
                    from gethsharding_tpu.mainchain.mirror import (
                        decode_record)

                    if snap["last_submitted"].get(shard_id) != period:
                        continue
                    rec = snap["records"].get(shard_id)
                    record = None if rec is None else decode_record(rec)
                else:
                    record = self.client.collation_record(shard_id, period)
                    if (record is not None and self.client
                            .last_submitted_collation(shard_id) != period):
                        record = None
                if record is None:
                    continue
                candidates.append((shard_id, period, record))
        if not candidates:
            return

        # phase 2: ONE batched proposer-signature verification across all
        # candidate shards (with sigbackend 'jax' this is a single vmapped
        # recovery-ladder dispatch, replacing the per-shard batch-of-1)
        signed = [c for c in candidates if c[2].signature]
        sig_ok = {}
        if signed:
            with tracing.span("notary/recover", rows=len(signed)):
                submit = getattr(self.sig_backend, "submit", None)
                if submit is not None:
                    # serving backend (--serving): the recovery batch runs
                    # on the serving tier's dispatch thread while THIS
                    # thread fires body-request broadcasts for
                    # not-yet-local collations — the syncer round trips
                    # overlap the device dispatch instead of queueing
                    # behind it. Fire-and-forget only: the authoritative
                    # (polling) availability check stays in submit_vote,
                    # so this adds zero stalls. (Requests for rows that
                    # then fail the signature gate are speculative but
                    # harmless: body fetches carry no vote authority.)
                    from gethsharding_tpu.serving.batcher import (
                        observe_future_wake)

                    digests, sigs = self._proposer_sig_inputs(signed)
                    future = submit("ecrecover_addresses", digests, sigs)
                    for shard_id, p, record in candidates:
                        self._prefetch_availability(shard_id, p, record)
                    recovered = future.result()
                    observe_future_wake(future)
                    results = self._match_proposers(recovered, signed)
                else:
                    results = self.verify_proposer_signatures(signed)
                for (shard_id, _, _), good in zip(signed, results):
                    sig_ok[shard_id] = good

        # phase 3: availability checks + signed vote submission per shard.
        # In sampled mode the checks happen FIRST, for every candidate at
        # once: k samples × all shards marshalled into ONE batched
        # das_verify_samples dispatch (the samples × shards plane), so
        # per-shard submit_vote reads a precomputed verdict instead of
        # issuing its own dispatch-of-k
        sampled_ok = (self._sampled_verdicts(candidates)
                      if self._sampled() else None)
        with tracing.span("notary/vote", candidates=len(candidates)):
            for shard_id, p, record in candidates:
                if record.signature and not sig_ok.get(shard_id, False):
                    self.signatures_rejected += 1
                    self.record_error(
                        f"proposer signature invalid: shard {shard_id} "
                        f"period {p}")
                    continue
                with self.m_validate_latency.time():
                    self.submit_vote(
                        shard_id, p, record, proposer_sig_checked=True,
                        availability=(None if sampled_ok is None
                                      else sampled_ok.get(shard_id,
                                                          False)))

    def _eligible_shards(self, shard_ids, snap=None) -> List[int]:
        """Committee eligibility for ALL shards from one sampling-context
        view: the reference issues an eth_call per shard per head
        (`notary.go:62`, the network-bound hot loop SURVEY.md §3.1 flags);
        here the keccak sampling runs locally over the fetched context —
        taken from the mirror snapshot when one is current, so a remote
        notary spends zero extra round trips on it. Falls back to
        per-shard calls when the backend lacks the view."""
        from gethsharding_tpu.crypto.keccak import keccak256

        if snap is not None:
            from gethsharding_tpu.mainchain.mirror import (
                decode_committee_context)

            ctx = decode_committee_context(snap["committee_context"])
        else:
            ctx = self.client.committee_context()
        me = self.client.account()
        if ctx is None:
            return [s for s in shard_ids
                    if self.client.get_notary_in_committee(s) == me]
        sample_size = ctx["sample_size"]
        if sample_size <= 0:
            return []
        registry = self.client.notary_registry()
        pool_index = registry.pool_index if registry is not None else 0
        prefix = ctx["blockhash"] + pool_index.to_bytes(32, "big")
        pool = ctx["pool"]
        me_raw = bytes(me)
        out = []
        for shard_id in shard_ids:
            digest = keccak256(prefix + shard_id.to_bytes(32, "big"))
            slot = int.from_bytes(digest, "big") % sample_size
            member = pool[slot] if slot < len(pool) else None
            if member is not None and member == me_raw:
                out.append(shard_id)
        return out

    # -- voting (notary.go:413 submitVote) ---------------------------------

    def submit_vote(self, shard_id: int, period: int, record,
                    proposer_sig_checked: bool = False,
                    availability: Optional[bool] = None) -> bool:
        registry = self.client.notary_registry()
        if registry is None or not registry.deposited:
            self.record_error("cannot vote: not a deposited notary")
            return False
        if registry.pool_index >= self.config.committee_size:
            self.record_error(
                f"invalid pool index {registry.pool_index}: exceeds committee "
                f"size {self.config.committee_size}"
            )
            return False
        # the crash-safe journal gate FIRST: it answers "did this
        # process lineage already submit (shard, period)?" locally, so
        # a restarted notary cannot double-vote even while its view of
        # the chain (or the chain connection itself) is catching up
        if self.journal is not None and self.journal.has_vote(shard_id,
                                                              period):
            return False
        if self.client.has_voted(shard_id, registry.pool_index):
            if self.journal is not None:
                # the chain knows but the journal missed it (vote landed
                # in the crash window): sync so the NEXT check is local
                self.journal.record_vote(shard_id, period)
            return False

        # proposer-signature check through the sig backend (the reference's
        # native-crypto seam). The period flow pre-verifies ALL candidate
        # records in one batch (notarize_collations phase 2); this single
        # check covers direct callers. An unsigned record (empty sig) is
        # accepted for parity with the reference flow, where header
        # signatures are not yet enforced on-chain — but a PRESENT
        # signature must recover to the proposer.
        if record.signature and not proposer_sig_checked:
            if not self.verify_proposer_signatures(
                    [(shard_id, period, record)])[0]:
                self.signatures_rejected += 1
                self.record_error(
                    f"proposer signature invalid: shard {shard_id} "
                    f"period {period}")
                return False

        # data-availability check: full mode checks the local shardDB and
        # fetches the body over shardp2p when missing (the reference's
        # syncer round-trip); sampled mode (--da-mode=sampled) verifies k
        # sampled chunk proofs against the proposer's erasure-extension
        # commitment instead — zero body bytes. The period flow passes a
        # precomputed batched verdict via `availability`; direct callers
        # compute their own here.
        with tracing.span("notary/verify", shard=shard_id):
            if availability is None:
                availability = (
                    self._check_sampled(shard_id, period, record)
                    if self._sampled()
                    else self._check_availability(shard_id, period,
                                                  record))
            if not availability:
                self.record_error(
                    f"collation body unavailable for shard {shard_id} "
                    f"period {period}"
                )
                return False

            # enforced windback (sharding/README.md): the previous W
            # periods' collations on this shard chain must also be
            # available before we extend it with a vote
            if not self._check_windback(shard_id, period):
                return False

        # the vote carries our aggregatable BLS signature over
        # (shard, period, chunkRoot) — the artifact the period audit
        # batch-verifies (smc/state_machine.py vote_digest)
        digest = vote_digest(shard_id, period, record.chunk_root)
        try:
            self.client.submit_vote(shard_id, period, registry.pool_index,
                                    record.chunk_root,
                                    bls_sig=self.client.bls_sign(digest))
        except SMCRevert as exc:
            self.record_error(f"vote reverted: {exc}")
            return False
        if self.journal is not None:
            # journal AFTER the chain accepted: the journal answers
            # "already submitted?", the chain stays authoritative
            self.journal.record_vote(shard_id, period)
        self.votes_submitted += 1
        self.m_votes.inc()

        # on quorum, persist the canonical header (notary.go:165)
        if self.client.last_approved_collation(shard_id) == period:
            self._set_canonical(shard_id, period, record)
        return True

    # -- the batched period audit (the re-architected hot loop) ------------

    def audit_period(self, period: int) -> Optional[bool]:
        """Verify a whole period's committee votes in ONE device dispatch.

        For every shard with a collation record in `period`, aggregate the
        accepted votes' BLS signatures and the voters' registered pubkeys,
        then verify all shards' aggregates in a single sig-backend call
        (with sigbackend 'jax': one batched optimal-ate pairing dispatch —
        BASELINE.md config 3, the loop `sharding/notary/notary.go:62`
        re-architected). The quorum outcome recomputed from the verified
        votes must be byte-identical with the SMC's `is_elected` flags;
        a mismatch (forged/invalid stored signature, tally drift) is
        counted and reported. Additionally replays the period's accepted
        vote transactions through the fixed-shape batch kernel
        (`ops/smc_jax.submit_votes_batch`) via the chain's vote log and
        checks state parity with the scalar machine.

        Returns True (all consistent), False (mismatch), or None (nothing
        auditable this period).
        """
        return self.audit_periods([period])[period]

    def _overlap_enabled(self) -> bool:
        """GETHSHARDING_NOTARY_OVERLAP (default on): fire the audit
        dispatch asynchronously and pull the verdict only when it is
        needed, overlapping device execution with host work."""
        return os.environ.get("GETHSHARDING_NOTARY_OVERLAP", "1") != "0"

    def audit_periods(self, periods, overlap: bool = False) -> dict:
        """Audit MANY periods in ONE sig-backend dispatch.

        The catch-up form of `audit_period` (an observer or light server
        re-validating history): rows from every period share a single
        batched aggregation+pairing call, so K periods cost one
        SIGNATURE dispatch of K×shards rows instead of K — on a
        latency-bound kernel nearly the cost of one. (The per-period SMC
        vote-log replay check remains one `verify_period_batch` call per
        period; its kernel shapes are period-local.) Returns
        {period: True/False/None} with `audit_period` semantics.

        ``overlap=True`` switches to the PIPELINED form: one dispatch
        per period, fired through the backend's async face, so period
        N+1's host marshalling/staging (and period N's verdict judging)
        runs while period N executes on device. Verdicts are identical;
        pick batched for a latency-bound kernel (fewer dispatches),
        overlapped when host marshalling is the bottleneck or verdicts
        should stream per period.
        """
        periods = list(periods)
        collected = {p: self._collect_audit_rows(p) for p in periods}
        results: dict = {p: None for p in periods}
        if overlap:
            return self._audit_periods_overlapped(periods, collected,
                                                  results)
        msgs, sig_rows, pk_rows, pk_keys = [], [], [], []
        spans = {}
        for period, rows in collected.items():
            if rows is None:
                continue
            start = len(msgs)
            msgs.extend(rows["msgs"])
            sig_rows.extend(rows["sig_rows"])
            pk_rows.extend(rows["pk_rows"])
            pk_keys.extend(rows["pk_keys"])
            spans[period] = (start, len(msgs))

        if not spans:
            return results
        # aggregation + verification are ONE backend call: with sigbackend
        # 'jax' the per-shard point sums AND the batched pairing happen in
        # a single device dispatch (no host point arithmetic per vote)
        with tracing.span("notary/audit", periods=len(spans),
                          rows=len(msgs)):
            with self.m_audit_latency.time():
                # the period audit is bulk traffic: behind a serving
                # tier it must coalesce under the bulk_audit admission
                # class (weighted share, shed before interactive), and
                # the thread-local tag survives the failover/soundness
                # wrapper composition in between
                with admission_class(CLASS_BULK_AUDIT):
                    ok = self.sig_backend.bls_verify_committees(
                        msgs, sig_rows, pk_rows, pk_row_keys=pk_keys)
        self.audits_run += len(spans)
        for period, (start, end) in spans.items():
            results[period] = self._judge_period(
                period, collected[period], ok[start:end])
        return results

    def _audit_periods_overlapped(self, periods, collected,
                                  results) -> dict:
        """The marshal/dispatch pipeline: submit every period's dispatch
        through the async backend face (each submit returns once the
        device is launched, so period N+1 marshals while N executes),
        then judge verdicts in order — each `result()` pull overlaps
        the remaining periods' device work."""
        pending = []  # (period, rows, verdict future)
        n_rows = sum(len(r["msgs"]) for r in collected.values()
                     if r is not None)
        with tracing.span("notary/audit", periods=len(periods),
                          rows=n_rows, overlap=True):
            # the latency timer covers submits + verdict pulls ONLY —
            # judging (incl. the per-period replay check) stays outside,
            # like the sync branch, so notary/period_audit_latency is
            # comparable between the batched and overlapped modes
            verdicts = []
            with self.m_audit_latency.time():
                for period in periods:
                    rows = collected[period]
                    if rows is None:
                        continue
                    # bulk_audit admission class (see audit_periods)
                    with admission_class(CLASS_BULK_AUDIT):
                        future = (self.sig_backend
                                  .bls_verify_committees_async(
                                      rows["msgs"], rows["sig_rows"],
                                      rows["pk_rows"],
                                      pk_row_keys=rows["pk_keys"]))
                    pending.append((period, rows, future))
                for period, rows, future in pending:
                    verdicts.append((period, rows, future.result()))
            for period, rows, ok in verdicts:
                results[period] = self._judge_period(period, rows, ok)
        self.audits_run += len(pending)
        return results

    def _begin_period_audit(self, period: int) -> Callable[[], None]:
        """Fire one period's audit dispatch NOW; returns the finalize
        closure that pulls the verdict and judges it. The head loop
        calls finalize after the vote phases, so the device verifies
        the previous period underneath the current period's votes. The
        audit-latency timer records submit + collect time only — the
        overlapped middle belongs to the vote phases, not the audit."""
        with tracing.span("notary/audit_submit", period=period):
            collected = self._collect_audit_rows(period)
            if collected is None:
                return lambda: None
            # the latency timer mirrors the sync path's scope — the
            # sig-backend call only: row collection stays before it and
            # judging (incl. the replay dispatch) after, so the metric
            # keeps one meaning across GETHSHARDING_NOTARY_OVERLAP
            t0 = time.monotonic()
            # bulk_audit admission class (see audit_periods)
            with admission_class(CLASS_BULK_AUDIT):
                future = self.sig_backend.bls_verify_committees_async(
                    collected["msgs"], collected["sig_rows"],
                    collected["pk_rows"], pk_row_keys=collected["pk_keys"])
            submit_s = time.monotonic() - t0

        def finish() -> None:
            with tracing.span("notary/audit_collect", period=period):
                t1 = time.monotonic()
                ok = future.result()
                self.m_audit_latency.observe(
                    submit_s + (time.monotonic() - t1))
                self.audits_run += 1
                self._judge_period(period, collected, ok)

        return finish

    def _collect_audit_rows(self, period: int) -> Optional[dict]:
        """One bulk pull of a period's auditable rows (or None)."""
        from gethsharding_tpu.rpc import codec
        from gethsharding_tpu.utils.hexbytes import Hash32

        # ONE bulk pull: records + vote sigs + voter pubkeys, resolved by
        # the attribution recorded AT VOTE TIME (pool slots can be freed/
        # reused before the audit runs; registry entries persist until
        # release). Remote backends serve this in a single round trip
        # (shard_auditData) instead of O(shards) record reads + O(votes)
        # registry lookups.
        data = self.client.audit_data(period)
        raw = bool(data.get("raw"))  # in-process pull: no hex wire codec
        shards, msgs, sig_rows, pk_rows, pk_keys = [], [], [], [], []
        signed_counts, total_counts, expected = [], [], []
        for shard_id in sorted(data["shards"]):
            rec = data["shards"][shard_id]
            member_pks, sigs, key_parts = [], [], []
            for vote in rec["votes"]:
                pk = (vote["pubkey"] if raw
                      else codec.dec_g2(vote["pubkey"]))
                if pk is None:
                    member_pks = None  # released voter: not resolvable
                    break
                member_pks.append(pk)
                sigs.append(vote["sig"] if raw
                            else codec.dec_g1(vote["sig"]))
                # transport-independent cache key: the decoded point's
                # int limbs identify the row's pubkeys either way
                x, y = pk
                key_parts.extend((x.a, x.b, y.a, y.b))
            if member_pks is None:
                continue
            shards.append(shard_id)
            root = (Hash32(rec["chunk_root"]) if raw
                    else Hash32(bytes.fromhex(rec["chunk_root"])))
            msgs.append(vote_digest(shard_id, period, root))
            sig_rows.append(sigs)
            pk_rows.append(member_pks)
            # the decoded pubkey limbs uniquely determine the row: the
            # backend caches the marshalled row under this key, so a
            # repeat committee (the steady state) skips the G2 limb
            # conversion entirely
            pk_keys.append(tuple(key_parts))
            signed_counts.append(len(rec["votes"]))
            total_counts.append(rec["vote_count"])
            expected.append(bool(rec["is_elected"]))
        if not shards:
            return None
        return {"shards": shards, "msgs": msgs, "sig_rows": sig_rows,
                "pk_rows": pk_rows, "pk_keys": pk_keys,
                "signed_counts": signed_counts,
                "total_counts": total_counts, "expected": expected}

    def _judge_period(self, period: int, rows: dict, ok) -> bool:
        """Outcome checks for one period's verified rows (`ok` aligns
        with rows["shards"])."""
        shards = rows["shards"]
        signed_counts = rows["signed_counts"]
        total_counts = rows["total_counts"]
        expected = rows["expected"]
        verified = sum(n for n, good in zip(signed_counts, ok) if good)
        self.aggregate_sigs_verified += verified
        self.m_sigs_verified.inc(verified)

        consistent = True
        quorum = self.config.quorum_size
        for shard_id, good, n_signed, n_total, elected in zip(
                shards, ok, signed_counts, total_counts, expected):
            # two independent checks: (1) the signed aggregate must verify
            # (a failure means a stored signature is forged/corrupt);
            # (2) the SMC's election flag must match the quorum rule over
            # the persistent accepted-vote count. n_signed can lag n_total
            # when key-less (legacy-registered) notaries voted — their
            # votes count for quorum but cannot be signature-audited.
            mismatch = None
            if not good:
                mismatch = (f"invalid aggregate signature "
                            f"({n_signed}/{n_total} votes signed)")
            elif (n_total >= quorum) != elected:
                mismatch = (f"tally drift: votes={n_total} quorum={quorum} "
                            f"smc_elected={elected}")
            if mismatch is not None:
                consistent = False
                self.audit_mismatches += 1
                self.m_audit_mismatch.inc()
                self.record_error(
                    f"period {period} audit mismatch on shard {shard_id}: "
                    f"{mismatch}")

        # the replay check runs the jax batch kernel; skip it for pure-host
        # control planes (sigbackend 'python') to keep them accelerator-free.
        # Wrappers (serving tier, failover breaker, chaos injection) keep
        # the wrapped backend's nature: unwrap the whole chain.
        base = self.sig_backend
        while hasattr(base, "inner"):
            base = base.inner
        replay = (self.client.verify_period_batch(period)
                  if base.name == "jax" else None)
        if replay is False:
            consistent = False
            self.audit_mismatches += 1
            self.record_error(
                f"period {period} batch-replay mismatch: "
                f"submit_votes_batch disagrees with the scalar SMC")
        if self.journal is not None:
            # this period's audit is DONE (mismatches are reported, not
            # retried): persist the watermark so a restart skips it —
            # and prune vote entries for closed periods (a vote can
            # only target the CURRENT period, so anything older than
            # the audited one can never be resubmitted)
            self.journal.set_audit_high_water(period)
            self.journal.prune_votes(before_period=period)
        return consistent

    def verify_proposer_signatures(self, records) -> list:
        """Batch-verify proposer signatures over collation-header records.

        `records`: [(shard_id, period, record)]. The signed digest is the
        header hash with an EMPTY signature field (the proposer signs
        before add_sig — proposer.py create_collation). One backend
        dispatch covers the whole batch: with sigbackend 'jax' this is the
        vmapped recovery ladder over every shard's record at once.
        """
        digests, sigs = self._proposer_sig_inputs(records)
        recovered = self.sig_backend.ecrecover_addresses(digests, sigs)
        return self._match_proposers(recovered, records)

    @staticmethod
    def _proposer_sig_inputs(records) -> Tuple[list, list]:
        """(digests, sigs65) for a [(shard_id, period, record)] batch."""
        digests, sigs = [], []
        for shard_id, period, record in records:
            unsigned = CollationHeader(
                shard_id=shard_id,
                chunk_root=record.chunk_root,
                period=period,
                proposer_address=record.proposer,
            )
            digests.append(bytes(unsigned.hash()))
            sigs.append(record.signature)
        return digests, sigs

    @staticmethod
    def _match_proposers(recovered, records) -> list:
        return [
            got is not None and got == rec[2].proposer
            for got, rec in zip(recovered, records)
        ]

    # -- data-availability sampling (--da-mode=sampled) --------------------

    def _sampled(self) -> bool:
        return self.da_mode == "sampled" and self.das is not None

    def _sampled_verdicts(self, candidates) -> dict:
        """Availability verdicts for many (shard, period, record) rows
        from ONE batched `das_verify_samples` dispatch.

        Per candidate: fetch the proposer's commitment + the notary's k
        deterministic sampled (chunk, proof) rows over shardp2p
        (das/service.collect_rows — retry + chaos seams inside), then
        verify EVERY candidate's samples in a single sig-backend call
        (with sigbackend 'jax': one keccak-lane dispatch over samples ×
        shards). A shard is available iff its commitment resolved and
        every one of its samples verified; missing samples were
        synthesized as invalid rows, so they fail loudly rather than
        shrink k."""
        verdicts = {}
        fresh = []
        account = bytes(self.client.account())
        for shard_id, period, record in candidates:
            if self._da_verdicts.get((shard_id, period)):
                verdicts[shard_id] = True  # immutable content: cached
                continue
            fresh.append((shard_id, period, record))
        # fire every candidate's commitment request up front so the
        # serial per-shard collect below mostly finds parked responses
        # instead of paying a broadcast round trip per shard
        if fresh:
            self.das.prefetch_commitments(
                [(shard_id, period) for shard_id, period, _ in fresh])
        if getattr(self.das, "proof_mode", "merkle") == "poly":
            return self._poly_verdicts(fresh, account, verdicts)
        collected = []
        for shard_id, period, record in fresh:
            rows = self.das.collect_rows(shard_id, period, record,
                                         account)
            collected.append((shard_id, period, rows))
        chunks, indices, proofs, roots = [], [], [], []
        spans = {}
        for shard_id, _, rows in collected:
            if rows is None:
                continue
            start = len(chunks)
            chunks.extend(rows["chunks"])
            indices.extend(rows["indices"])
            proofs.extend(rows["proofs"])
            roots.extend(rows["roots"])
            spans[shard_id] = (start, len(chunks))
        ok: list = []
        if chunks:
            with tracing.span("notary/das_verify", rows=len(chunks),
                              shards=len(spans)):
                ok = self.sig_backend.das_verify_samples(
                    chunks, indices, proofs, roots)
        for shard_id, period, rows in collected:
            if rows is None:
                verdicts[shard_id] = False  # no commitment: unavailable
                continue
            start, end = spans[shard_id]
            row_ok = ok[start:end]
            self.das.note_verdicts(row_ok)
            good = bool(row_ok) and all(row_ok)
            verdicts[shard_id] = good
            if good:
                self._da_verdicts[(shard_id, period)] = True
        if len(self._da_verdicts) > self._DA_CACHE_MAX:
            # prune oldest periods first: closed periods stop being
            # re-checked once the head loop moves on anyway
            for key in sorted(self._da_verdicts,
                              key=lambda sp: sp[1])[:len(self._da_verdicts)
                                                    - self._DA_CACHE_MAX]:
                del self._da_verdicts[key]
        return verdicts

    def _poly_verdicts(self, fresh, account: bytes, verdicts: dict) -> dict:
        """The --da-proofs=poly phase-3: ONE `das_verify_multiproofs`
        row per candidate shard (constant-size proof per collation, the
        whole period folded into one batched pairing dispatch). The
        same availability semantics as the merkle path: no commitment
        -> unavailable; a failed or merkle-only fetch was synthesized
        as an invalid row by `collect_poly_row`, so it scores False."""
        collected = []
        for shard_id, period, record in fresh:
            row = self.das.collect_poly_row(shard_id, period, record,
                                            account)
            collected.append((shard_id, period, row))
        batched = [(shard_id, period, row)
                   for shard_id, period, row in collected
                   if row is not None]
        ok: list = []
        if batched:
            with tracing.span("notary/das_poly_verify",
                              rows=len(batched)):
                ok = self.sig_backend.das_verify_multiproofs(
                    [row["poly_commitment"] for _, _, row in batched],
                    [row["indices"] for _, _, row in batched],
                    [row["evals"] for _, _, row in batched],
                    [row["proof"] for _, _, row in batched],
                    [row["n"] for _, _, row in batched])
        it = iter(ok)
        row_verdicts = {shard_id: next(it)
                        for shard_id, _, _ in batched}
        for shard_id, period, row in collected:
            if row is None:
                verdicts[shard_id] = False  # no commitment: unavailable
                continue
            good = bool(row_verdicts.get(shard_id, False))
            self.das.note_verdicts([good])
            verdicts[shard_id] = good
            if good:
                self._da_verdicts[(shard_id, period)] = True
        if len(self._da_verdicts) > self._DA_CACHE_MAX:
            for key in sorted(self._da_verdicts,
                              key=lambda sp: sp[1])[:len(self._da_verdicts)
                                                    - self._DA_CACHE_MAX]:
                del self._da_verdicts[key]
        return verdicts

    # one verdict per (shard, period): 100 shards x a 40-period horizon
    # fits with room; entries are a bool each
    _DA_CACHE_MAX = 4096

    def _check_sampled(self, shard_id: int, period: int, record) -> bool:
        """The single-shard sampled check (direct submit_vote callers;
        the period flow batches across shards instead)."""
        return self._sampled_verdicts(
            [(shard_id, period, record)]).get(shard_id, False)

    def _check_windback(self, shard_id: int, period: int) -> bool:
        """Enforced windback: verify availability of the last
        `config.windback_depth` periods' collations on this shard chain
        (fetching missing bodies over shardp2p), refusing to vote while
        any of them is unavailable.

        Prior-period records come from the mirror snapshot's
        `prior_records` (closed periods are immutable, so the bulk pull
        is exact) — a remote notary pays ZERO extra round trips here;
        only periods outside the snapshot's depth fall back to direct
        `collation_record` reads."""
        depth = self.config.windback_depth
        if depth <= 0:
            return True
        from gethsharding_tpu.mainchain.mirror import decode_record

        snap = self.mirror.snapshot() if self.mirror is not None else None
        prior_records = (snap or {}).get("prior_records") or {}
        if snap is not None and (snap.get("period") or 0) != period:
            prior_records = {}  # stale snapshot: its window may not align
        for prior in range(max(1, period - depth), period):
            if prior in prior_records:
                rec = prior_records[prior].get(shard_id)
                record = None if rec is None else decode_record(rec)
            else:
                record = self.client.collation_record(shard_id, prior)
            if record is None:
                continue  # no collation that period: nothing to hold
            self.m_windback_checks.inc()
            # sampled mode holds the windback by proof too: prior
            # periods are re-sampled, never body-fetched
            held = (self._check_sampled(shard_id, prior, record)
                    if self._sampled()
                    else self._check_availability(shard_id, prior, record))
            if not held:
                self.record_error(
                    f"windback: collation body unavailable for shard "
                    f"{shard_id} period {prior}; refusing to vote")
                return False
        return True

    def _availability_probe(self, shard_id: int, period: int, record):
        """(header, verdict): the shardDB's LOCAL answer. True/False is
        authoritative; None means the body is not local (ShardError), in
        which case the body request has been broadcast over shardp2p —
        fire-and-forget, never blocks."""
        header = self._reconstruct_header(shard_id, period, record)
        try:
            return header, self.shard.check_availability(header)
        except ShardError:
            pass
        if self.p2p is not None:
            self.p2p.broadcast(
                CollationBodyRequest(
                    chunk_root=record.chunk_root,
                    shard_id=shard_id,
                    period=period,
                    proposer=record.proposer,
                )
            )
        return header, None

    def _prefetch_availability(self, shard_id: int, period: int,
                               record) -> None:
        """Fire the body request for a not-yet-local collation NOW so
        the responding syncer's round trip runs concurrently with
        whatever this thread overlaps it with; `_check_availability`
        remains the authoritative (polling) gate. In sampled DA mode
        this is a no-op — the whole point is that NO body request ever
        leaves a sampled notary (the sampled check fetches k
        chunks+proofs in phase 3 instead)."""
        if self._sampled():
            return
        self._availability_probe(shard_id, period, record)

    def _check_availability(self, shard_id: int, period: int, record) -> bool:
        header, verdict = self._availability_probe(shard_id, period, record)
        if verdict is not None:
            return verdict
        if self.p2p is None:
            return False

        # body not local: poll briefly for the responding syncer's
        # asynchronous store, under the body-fetch retry policy — every
        # retry RE-BROADCASTS the request (via the probe), so one lost
        # frame or one slow peer costs a capped backoff, not the vote
        def attempt() -> bool:
            got = poll_probe(
                lambda: self.shard.check_availability(header), self.wait,
                interval_s=0.05, polls=7, not_ready=(ShardError,))
            if got is not POLL_MISS:
                return got
            _, late = self._availability_probe(shard_id, period, record)
            if late is not None:
                return late
            raise _BodyUnavailable(
                f"shard {shard_id} period {period} body not delivered")

        try:
            return self._body_retry.call(attempt)
        except (_BodyUnavailable, FetchAborted):
            return False

    def _reconstruct_header(self, shard_id: int, period: int,
                            record) -> CollationHeader:
        return CollationHeader(
            shard_id=shard_id,
            chunk_root=record.chunk_root,
            period=period,
            proposer_address=record.proposer,
            proposer_signature=record.signature,
        )

    def _set_canonical(self, shard_id: int, period: int, record) -> None:
        if self._sampled():
            # a sampled notary verified availability by proof — it holds
            # no body, and the shardDB canonical index requires one.
            # Body-holding nodes (proposer, observer) index canonical.
            return
        header = self._reconstruct_header(shard_id, period, record)
        try:
            if self.shard.shard_id == shard_id:
                # the header is reconstructed from the on-chain record; make
                # sure it is persisted locally before indexing it canonical
                self.shard.save_header(header)
                self.shard.set_canonical(header)
                self.canonical_set += 1
                self.log.info("Canonical header set: shard %s period %s",
                              shard_id, period)
        except ShardError as exc:
            self.record_error(f"set canonical failed: {exc}")
