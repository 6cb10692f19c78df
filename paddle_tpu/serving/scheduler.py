"""Request lifecycle + continuous-batching scheduler.

Policy (vLLM-style, adapted to the static-slot decode program):

* **Admission** is FCFS from the waiting deque: a request is admitted
  when a decode slot is open and the pool can hand it blocks for its
  whole current prefix (prompt + any tokens generated before a
  preemption) plus the first decode token; in a group of window
  layers, whose blocks come with the context and go home behind the
  band, when the group has room for what every running request and this
  one hold at most (a count: band and one program's run each).
  Preempted requests rejoin the FRONT of the queue, so an eviction never
  costs a request its place in line.
* **Preemption** is LIFO — when a running request needs one more block
  and the pool is dry, the YOUNGEST other running request is evicted
  (recompute-style: its blocks are freed now, its prefix re-prefills on
  readmission).  Oldest-first eviction would starve the head of the
  line; evicting the youngest bounds any request's preemption count by
  the pool's churn, which is the fairness half of the admission story.
* **Starvation guard (aging)**: under the router's sustained load, LIFO
  eviction plus front-of-queue resume can ping-pong two block-hungry
  requests forever.  A request that has been preempted or head-of-line
  blocked ``promote_after`` times total is PROMOTED: it becomes immune
  to preemption by non-promoted requests (promoted requesters may still
  evict each other, so the pool can never deadlock), breaking the
  livelock while keeping eviction cheap for the common case.  Each
  promotion steps ``serving_starvation_promotions_total``.
* **Deadlines**: a request may carry ``queue_deadline_s`` (max
  continuous wait in the queue, re-armed on preemption requeue) and
  ``ttl_s`` (max total lifetime from arrival — failover resubmission
  preserves the original arrival).  The engine sweeps both at the top
  of every step; expiry is a CLEAN finish: blocks freed, ``on_finish``
  fired with ``finish_reason`` ``expired-queue`` / ``expired-ttl``.
* **Prefill/decode split**: prefill happens in bounded chunks
  (`prefill_chunk` tokens per engine step), so a long prompt occupies
  the prefill lane for many steps while every decode-ready request
  still advances one token per step — in-flight decode never stalls
  behind admission.
"""
from __future__ import annotations

import collections

from ..observability import trace as _trace

WAITING = "waiting"
RUNNING = "running"
PREEMPTED = "preempted"
FINISHED = "finished"
FAILED = "failed"
EXPIRED = "expired"


def clock():
    """Seconds on the span recorder's clock (Unix time, monotonic): every
    mark of a request is taken on it, so that a request's life and the
    engine steps that served it can be laid side by side."""
    return _trace.now_ns() * 1e-9


class Request:
    """One generation request moving through the engine."""

    _next_id = 0

    def __init__(self, prompt_ids, max_new_tokens=20, eos_token_id=None,
                 do_sample=False, temperature=1.0, top_k=None, top_p=None,
                 seed=0, on_token=None, on_finish=None, resume_tokens=None,
                 arrival_t=None, queue_deadline_s=None, ttl_s=None):
        self.id = Request._next_id
        Request._next_id += 1
        self.prompt = [int(t) for t in prompt_ids]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.seed = int(seed)
        self.on_token = on_token
        self.on_finish = on_finish

        self.state = WAITING
        # `resume_tokens` seeds `generated` with tokens a PRIOR replica
        # already produced (router failover): re-prefill streams
        # prompt+generated and decode continues at the next position —
        # the same path a preemption-resume takes, so the continuation
        # is token-identical to never having moved.
        self.generated = [int(t) for t in (resume_tokens or [])]
        # resumed means "a prior replica served part of this stream" —
        # true even when the resume list is EMPTY (a failover after one
        # emitted token trims the whole overlap away), so the replica-
        # local TTFT observation is still suppressed
        self.resumed = resume_tokens is not None
        # pool block ids, position-ordered: one table a block group of
        # the pool (`Scheduler.admit` makes them); `behind[g]` leading
        # entries of table g lie behind that group's window, have gone
        # home and hold no block any more
        self.block_tables = [[]]
        self.behind = [0]
        # the request's entry in the pool's per-request planes (a
        # recurrent state), where the model has any
        self.state_slot = None
        self.ctx = 0                # tokens whose K/V live in the pool
        # picks the engine has dispatched and not emitted yet (it runs
        # one decode program ahead of the host), and this request's row
        # in the newest of those programs
        self.in_flight = 0
        self.slot = None
        self.finish_reason = None
        self.poisoned = False       # chaos serving.request_poison
        self.preemptions = 0
        self.admit_skips = 0        # head-of-line blocked admit passes
        self.promoted = False       # starvation guard: victim immunity

        # marks, in seconds on `clock()`; the engine writes them out as
        # one `serving.request` span when the request finishes
        self.arrival_t = (clock() if arrival_t is None
                          else float(arrival_t))
        self.queued_t = clock()     # start of the CURRENT wait
        self.admitted_t = None      # first admission
        self.prefill_done_t = None  # the step that made it decode-ready
        self.queue_deadline_s = (None if queue_deadline_s is None
                                 else float(queue_deadline_s))
        self.ttl_s = None if ttl_s is None else float(ttl_s)
        self.first_token_t = None
        self.last_token_t = None

    # `feed` = every token the model must consume: the prompt plus all
    # generated tokens.  Invariant: `ctx` tokens have K/V in the pool;
    # feed[ctx] is the next input.  Prefill streams feed[0:feed_len-1]
    # into the pool in chunks; the decode step then consumes feed[ctx]
    # (the last prompt token on a fresh request, the newest generated
    # token afterwards), writes its K/V, and samples the next token —
    # ONE uniform decode path does all sampling.  A pick in flight
    # (`in_flight`) has advanced `ctx` at its dispatch and reaches
    # `generated` when it is emitted, a step later: until then
    # ctx == feed_len - 1 + in_flight.
    @property
    def block_table(self):
        """The table of the pool's first group (the whole context, where
        any layer keeps it).  An alias from before the groups that two
        older test lines read: to go with them at the next simplicity
        pass."""
        return self.block_tables[0]

    @property
    def feed_len(self):
        return len(self.prompt) + len(self.generated)

    @property
    def decode_ready(self):
        """Every token before the next input is in the pool or on its
        way there, and a token is left to pick: a row whose pick in
        flight is its last waits for it, so a length finish never
        dispatches a surplus row."""
        return (self.state == RUNNING
                and self.ctx == self.feed_len - 1 + self.in_flight
                and len(self.generated) + self.in_flight
                < self.max_new_tokens)

    @property
    def needs_prefill(self):
        """True while part of the prefix still has to stream into the
        pool (fresh admission, or re-prefill after preemption)."""
        return self.state == RUNNING and self.ctx < self.feed_len - 1

    def feed_tokens(self):
        return self.prompt + self.generated

    def expiry(self, now):
        """``"ttl"`` / ``"queue"`` when a deadline has passed, else
        None.  TTL counts from arrival (which failover preserves); the
        queue-wait deadline counts the CURRENT continuous wait only, so
        a preemption re-arms it rather than inheriting the whole
        history TTL already covers."""
        if self.ttl_s is not None and now - self.arrival_t > self.ttl_s:
            return "ttl"
        if (self.queue_deadline_s is not None
                and self.state in (WAITING, PREEMPTED)
                and now - self.queued_t > self.queue_deadline_s):
            return "queue"
        return None

    def __repr__(self):
        return (f"Request(id={self.id}, state={self.state}, "
                f"prompt={len(self.prompt)}, gen={len(self.generated)}, "
                f"ctx={self.ctx})")


class Scheduler:
    """Admission / eviction / preemption against the block pool."""

    def __init__(self, pool, max_running=8, promote_after=4, run_tokens=1):
        self.pool = pool
        self.max_running = int(max_running)
        # the most tokens one program writes for a request (the engine's
        # prefill chunk): with its window, what a request holds at most
        # in a window's group
        self.run_tokens = int(run_tokens)
        # skips (preemptions + head-blocked admit passes) before a
        # request is promoted out of the victim pool; 0/None disables
        self.promote_after = int(promote_after or 0)
        self.waiting = collections.deque()
        self.running = []           # admission-ordered (oldest first)

    @property
    def queue_depth(self):
        return len(self.waiting)

    def submit(self, req):
        req.state = WAITING
        req.queued_t = clock()
        self.waiting.append(req)

    def admit(self):
        """Move waiting requests into the running set while slots and
        blocks last.  Returns the newly admitted requests."""
        admitted = []
        while self.waiting and len(self.running) < self.max_running:
            req = self.waiting[0]
            if self.pool.slots and not self.pool.free_slots:
                from ..observability import metrics as _metrics
                _metrics.registry().counter(
                    "serving_state_slot_waits_total").inc()
                break
            # blocks for the whole prefix to re/prefill plus one decode
            # token, so admission can't strand a request mid-prefill.  A
            # window's group hands blocks out as the context reaches them
            # (`reserve`) and takes them back behind the band (`trim`):
            # there admission COUNTS what the running requests and this
            # one hold at most, so the group cannot run dry under them
            req.block_tables = [[] for _ in self.pool.groups]
            req.behind = [0] * len(self.pool.groups)
            if not (self._windows_hold(req) and self._extend(
                    req, req.feed_len + 1, windows=False)):
                # head-of-line blocks: stay FCFS, but count the skip —
                # a head stuck behind LIFO-resumed work ages toward
                # promotion just like a preemption victim
                self._release(req)
                req.admit_skips += 1
                self._maybe_promote(req)
                break
            self.waiting.popleft()
            req.state_slot = self.pool.allocate_slot()
            req.ctx = 0
            req.state = RUNNING
            self.running.append(req)
            admitted.append(req)
        return admitted

    def most_blocks(self, group, n_tokens):
        """The most blocks of `group` a request of `n_tokens` positions
        holds at a time: its whole table, or in a window's group its band
        and one program's run."""
        whole = self.pool.blocks_for(n_tokens)
        band = self.pool.band_blocks(group, self.run_tokens)
        return whole if band is None else min(whole, band)

    def _windows_hold(self, req):
        """Every window's group has room for what the running requests
        and `req` hold at most, each at once."""
        held = self.running + [req]
        return all(
            sum(self.most_blocks(g, len(r.prompt) + r.max_new_tokens)
                for r in held) <= grp.num_blocks
            for g, grp in enumerate(self.pool.groups)
            if grp.window is not None)

    def _extend(self, req, n_tokens, windows=True):
        """Blocks in every group's table (a window's only with
        `windows`) up to position `n_tokens`, or False when a group
        cannot give them now.  What was got stays in the tables."""
        need = self.pool.blocks_for(n_tokens)
        for g, table in enumerate(req.block_tables):
            if len(table) >= need or not (
                    windows or self.pool.groups[g].window is None):
                continue
            got = self.pool.allocate(need - len(table), g)
            if got is None:
                return False
            table.extend(got)
        return True

    def reserve(self, req, n_tokens):
        """Ensure `req` has, in every group, the blocks of the positions
        before `n_tokens` that a program is about to write; preempts the
        youngest OTHER running request when a group is dry.  Returns
        False when no space could be made (req should retry next
        step)."""
        while not self._extend(req, n_tokens):
            victim = self._pick_victim(exclude=req,
                                       allow_promoted=req.promoted)
            if victim is None:
                return False
            self.preempt(victim)
        return True

    def grow(self, req):
        """`reserve` for the position the request's next decode row
        writes (`ctx`: feed_len - 1, one further with a pick in
        flight)."""
        return self.reserve(req, req.ctx + 1)

    def trim(self, req):
        """Hand back the blocks of `req` that lie wholly behind their
        group's window, by the context of the program last DISPATCHED:
        a block is reused only by a program dispatched later, and
        programs run in dispatch order.  The band keeps one position of
        slack, so that a row the host feeds again (``ctx - 1``: its pick
        was not the program's) still finds every key it sees.  Returns
        the blocks freed."""
        freed = 0
        for g, grp in enumerate(self.pool.groups):
            if grp.window is None:
                continue
            table, done = req.block_tables[g], req.behind[g]
            upto = min(max(req.ctx - grp.window, 0) // self.pool.block_size,
                       len(table))
            if upto > done:
                self.pool.free(table[done:upto], g)
                table[done:upto] = [0] * (upto - done)
                req.behind[g] = upto
                freed += upto - done
        return freed

    def _pick_victim(self, exclude, allow_promoted=False):
        """Youngest running request that isn't `exclude` and isn't
        promoted.  A PROMOTED requester may fall back to evicting a
        promoted victim (youngest first) — promotion shields against
        un-promoted churn, never deadlocks the pool."""
        for cand in reversed(self.running):      # youngest admission last
            if cand is not exclude and not cand.promoted:
                return cand
        if allow_promoted:
            for cand in reversed(self.running):
                if cand is not exclude:
                    return cand
        return None

    def _maybe_promote(self, req):
        if (self.promote_after and not req.promoted
                and req.preemptions + req.admit_skips
                >= self.promote_after):
            req.promoted = True
            from ..observability import metrics as _metrics
            _metrics.registry().counter(
                "serving_starvation_promotions_total").inc()

    def rewind(self, req):
        """The request's context is built again from its first position
        (a cache that cannot step back): the tables of the window groups,
        whose early blocks have gone home, start empty again."""
        for g, grp in enumerate(self.pool.groups):
            if grp.window is not None:
                self.pool.free(req.block_tables[g][req.behind[g]:], g)
                req.block_tables[g], req.behind[g] = [], 0
        req.ctx = 0

    def _release(self, req):
        """The request's blocks of every group and its state slot go
        home."""
        for g, table in enumerate(req.block_tables):
            self.pool.free(table[req.behind[g]:], g)
        req.block_tables = [[] for _ in req.block_tables]
        req.behind = [0] * len(req.behind)
        if req.state_slot is not None:
            self.pool.free_slot(req.state_slot)
            req.state_slot = None

    def preempt(self, req):
        """Evict: free every block and the state slot now, requeue at the
        FRONT; the prefix (prompt + generated so far) re-prefills on
        readmission, a recurrent state from zero."""
        from ..observability import metrics as _metrics
        _metrics.registry().counter(
            "serving_requests_preempted_total").inc()
        self._release(req)
        req.ctx = 0
        req.preemptions += 1
        req.state = PREEMPTED
        req.queued_t = clock()      # re-arm the queue-wait clock
        self._maybe_promote(req)
        self.running.remove(req)
        self.waiting.appendleft(req)

    def finish(self, req, reason):
        self._release(req)
        if reason in ("eos", "length"):
            req.state = FINISHED
        elif reason == "error" or reason == "cancelled":
            req.state = FAILED
        else:                       # expired-queue / expired-ttl / drained
            req.state = EXPIRED
        req.finish_reason = reason
        if req in self.running:
            self.running.remove(req)
        try:
            self.waiting.remove(req)
        except ValueError:
            pass
