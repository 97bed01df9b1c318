"""Plain reference of the Raft deployment with log compaction (lab 2D).

One world at a time, in plain Python integers: the discrete-event loop
(earliest pending event first; equal times in slot order; freed slots
reused lowest first), the network model (per-message latency and loss
drawn at send time), crash faults, and Raft as the MIT 6.824 labs and
the Raft paper state it, with what lab 2D adds: each server compacts its
log into a snapshot every ``snapshot_interval`` applied entries, a leader
sends InstallSnapshot (Figure 13) to a follower its snapshot has passed,
and a steady stream of client commands arrives at every live server.
Every random decision draws from a counter-based Threefry-2x32 stream
keyed by the world's seed, so the same seed gives the same world.

How this deployment's servers behave, where the paper leaves a choice:

- A server's log is a list of ``(term, command)`` entries above its
  snapshot (last index, last term, and a digest of the commands it
  covers). A server applies an entry when it commits it; the digest of
  commands ``1..k`` is the sum mod 2^32 of ``entry_hash(i, command_i)``.
  A server compacts when its commit index crosses a multiple of the
  interval, and never holds more than ``log_cap`` entries above its
  snapshot.
- The election timer is a deadline: granting a vote or accepting a
  leader's message moves it to ``now + timeout``; the one pending timer
  re-arms at the deadline until it passes; an election that starts draws
  a fresh timeout from [elect_min, elect_max).
- A leader sends one entry per AppendEntries, at the follower's
  ``next_idx``, and moves ``next_idx`` past what it sent. It sends to
  every follower on a heartbeat, on winning and on taking a command; a
  reply that advances a follower's match while the follower has entries
  to receive, or that refuses (carrying the highest index the
  follower's log may share: its end, or below the refused entry's
  predecessor), sends that follower its next message at once, with
  ``next_idx`` stepped back below its last value and at most one above
  that index. A follower whose ``next_idx`` the snapshot covers is sent
  the snapshot.
- Command ``p`` (``p + 1`` as its value) arrives at every live server at
  ``propose_start + p * propose_interval``; only the leader appends it.
  Each server holds one pending arrival, re-armed by the last; a restarted
  server resumes at the first arrival at or after its restart.
- Crash recovery: term, vote, snapshot and log survive; the restarted
  server has applied exactly its snapshot.
- The bug flag: a second win of a term; two servers whose committed
  entries differ where both logs hold them, or whose digests differ at
  the highest index both have applied when that index is at or above
  both snapshots (lab 2D's ``checkLogs``); a server whose commit index
  passes its log's end.

It shares no code and no data with the system under test: it is the
yardstick the benchmark's ``correct`` compares the system's per-seed
observation rows against. ``control=`` breaks one guarantee of the
deployment on purpose, so that the comparison can be shown to fail.
"""
from __future__ import annotations

M32 = 0xFFFFFFFF
INF = 2 ** 31 - 1
STREAM = 16                       # the world's random stream id

FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
(ELECTION, HEARTBEAT, REQVOTE, VOTEREPLY, APPEND, APPENDREPLY, PROPOSE,
 INSTALL) = range(8)
TIMER, FAULT = 1, 2               # event flags
KILL, RESTART, CLOG_NODE, UNCLOG_NODE, CLOG_LINK, UNCLOG_LINK = range(6)
WON_BITS = 128                    # terms tracked for election safety

# The one guarantee the control breaks: a restarted server keeps its
# snapshot and log (lab 2D's persister, SaveStateAndSnapshot).
CONTROLS = ("restart_drops_snapshot",)

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)


def threefry2x32(k0: int, k1: int, c0: int, c1: int):
    """Threefry-2x32 with 20 rounds (Salmon et al., SC 2011)."""
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0, x1 = (c0 + k0) & M32, (c1 + k1) & M32
    for i in range(5):
        for r in range(4):
            x0 = (x0 + x1) & M32
            rot = _ROT[4 * (i % 2) + r]
            x1 = (((x1 << rot) & M32) | (x1 >> (32 - rot))) ^ x0
        x0 = (x0 + ks[(i + 1) % 3]) & M32
        x1 = (x1 + ks[(i + 2) % 3] + i + 1) & M32
    return x0, x1


def entry_hash(index: int, command: int) -> int:
    """A command's term in the state machine's digest (32-bit mix)."""
    x = ((index * 0x9E3779B1) & M32) ^ (command & M32)
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    return x ^ (x >> 13)


class Stream:
    """Draw ``i`` of a world's stream is word 0 of Threefry(key, (i, 0))."""

    def __init__(self, seed: int):
        self.k0, self.k1 = threefry2x32(seed & M32, (seed >> 32) & M32,
                                        STREAM, 0)
        self.counter = 0

    def at(self, i: int) -> int:
        return threefry2x32(self.k0, self.k1, i & M32, 0)[0]

    def take(self) -> int:
        x = self.at(self.counter)
        self.counter += 1
        return x


class Event:
    __slots__ = ("time", "kind", "flags", "src", "dst", "gen", "payload")

    def __init__(self, time, kind, flags, src, dst, gen, payload):
        self.time, self.kind, self.flags = time, kind, flags
        self.src, self.dst, self.gen = src, dst, gen
        self.payload = payload


class Server:
    """One Raft server's state."""

    def __init__(self, n: int):
        self.term, self.voted_for, self.role, self.votes = 0, -1, FOLLOWER, 0
        self.commit = 0
        self.snap, self.snap_term, self.snap_digest = 0, 0, 0
        self.applied_digest = 0
        self.log = []                         # entries snap+1 .. last()
        self.next_idx = [1] * n
        self.match_idx = [0] * n
        self.epoch = 0
        self.timeout = self.deadline = 0
        self.won = set()

    def last(self) -> int:
        return self.snap + len(self.log)

    def entry(self, k: int):
        """(term, command) of entry ``k``, ``snap < k <= last()``; an
        index outside the log reads (0, 0)."""
        if self.snap < k <= self.last():
            return self.log[k - self.snap - 1]
        return (0, 0)

    def term_at(self, k: int) -> int:
        return self.snap_term if k == self.snap else self.entry(k)[0]

    def digest(self, k: int) -> int:
        """Digest of commands 1..k, for snap <= k <= last()."""
        d = self.snap_digest
        for i in range(self.snap + 1, k + 1):
            d += entry_hash(i, self.entry(i)[1])
        return d & M32


class World:
    """One seeded simulation of a Raft cluster with log compaction.

    ``engine`` and ``raft`` are the deployment's two parameter groups as
    the configuration file states them; ``faults`` is this world's list
    of ``[time_us, op, a, b]`` rows (rows with time < 0 are disabled).
    """

    def __init__(self, seed: int, engine: dict, raft: dict, faults=(),
                 control: str | None = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.control = control
        n = self.n = int(raft["n"])
        if int(engine["n_nodes"]) != n:
            raise ValueError("engine n_nodes and raft n differ")
        self.L = int(raft["log_cap"])
        self.interval = int(raft["snapshot_interval"])
        if self.interval <= 0:
            raise ValueError("this reference needs snapshot_interval > 0")
        self.qcap = int(engine["queue_cap"])
        self.t_limit = int(engine["t_limit_us"])
        self.lat_min = int(engine["latency_min_us"])
        self.lat_max = int(engine["latency_max_us"])
        self.loss = float(engine["loss_rate"])
        self.stop_on_bug = bool(engine["stop_on_bug"])
        # n peer messages, the election/heartbeat timer, the client timer
        self.outbox = n + 2
        self.elect = (int(raft["elect_min_us"]), int(raft["elect_max_us"]))
        self.heartbeat = int(raft["heartbeat_us"])
        self.double_vote = bool(raft["buggy_double_vote"])
        self.n_cmds = int(raft["n_proposals"])
        self.cmd_start = int(raft["propose_start_us"])
        self.cmd_gap = int(raft["propose_interval_us"])
        self.rng = Stream(seed)

        self.now = 0
        self.slots = [None] * self.qcap
        self.alive = [True] * n
        self.gen = [0] * n
        self.clog_node = [False] * n
        self.clog_link = [[False] * n for _ in range(n)]
        self.active = True
        self.steps = self.delivered = self.dropped = 0
        self.overflow = self.bug = False
        self.bug_time = INF
        self.first_leader = INF
        self.elections_won = 0
        self.snapshots = self.installs = 0

        self.s = [Server(n) for _ in range(n)]
        init = []
        for i in range(n):
            d = self._election_delay()
            self.s[i].timeout = self.s[i].deadline = d
            init.append(Event(d, ELECTION, TIMER, i, i, 0, [0]))
        if self.n_cmds:
            for i in range(n):
                init.append(Event(self.cmd_start, PROPOSE, TIMER, i, i, 0,
                                  [1]))
        for t, op, a, b in faults:
            if t >= 0:
                if op not in (KILL, RESTART, CLOG_NODE, UNCLOG_NODE,
                              CLOG_LINK, UNCLOG_LINK):
                    raise ValueError(f"fault op {op} is not modelled")
                init.append(Event(int(t), int(op), FAULT, int(a), int(b),
                                  0, []))
        for ev in init:
            self._push(ev)
        self.qmax = self.depth = sum(s is not None for s in self.slots)

    # -- the event queue -------------------------------------------------
    def _push(self, ev: Event) -> int:
        if ev.time >= INF:
            return 0
        for i, s in enumerate(self.slots):
            if s is None:
                self.slots[i] = ev
                return 1
        self.overflow = True
        return 0

    def _pop(self):
        best = None
        for i, s in enumerate(self.slots):
            if s is not None and (best is None
                                  or s.time < self.slots[best].time):
                best = i
        if best is None:
            return None
        ev, self.slots[best] = self.slots[best], None
        return ev

    def _election_delay(self) -> int:
        lo, hi = self.elect
        return lo + self.rng.take() % (hi - lo)

    # -- one step ----------------------------------------------------------
    def step(self) -> None:
        """Process the earliest pending event; a finished world is left
        as it is."""
        if not self.active:
            return
        n = self.n
        self.steps += 1
        ev = self._pop()
        found = ev is not None
        if found:
            self.now = max(self.now, ev.time)
        in_time = self.now < self.t_limit
        sends, src, hbug = [], 0, False
        delivered = False
        if found and in_time:
            dst = min(max(ev.dst, 0), n - 1)
            if ev.flags & FAULT:
                src = min(max(ev.src, 0), n - 1)
                sends = self._fault(ev.kind, ev.src, ev.dst)
            elif (ev.flags & TIMER and ev.gen != self.gen[dst] % 256) \
                    or not self.alive[dst]:
                self.dropped += 1
            else:
                delivered = True
                self.delivered += 1
                src = dst
                sends, hbug = self._handle(ev, dst)
        inserted = self._send(src, sends)
        self.depth += inserted - found
        self.qmax = max(self.qmax, self.depth)
        if (delivered and hbug) or self._unsafe():
            if not self.bug:
                self.bug_time = self.now
            self.bug = True
        self.active = found and in_time \
            and not (self.stop_on_bug and self.bug)

    def run(self, max_steps: int | None = None) -> "World":
        while self.active and (max_steps is None or self.steps < max_steps):
            self.step()
        return self

    def _send(self, src: int, sends) -> int:
        """Queue a handler's sends. ``sends`` is a list of ``(slot, kind,
        dst, delay_or_None, payload)``; ``delay`` is given for timers,
        messages draw a latency. Each outbox slot owns two draws (latency,
        loss) whether it sends or not, and slots are queued in order."""
        base = self.rng.counter
        self.rng.counter += 2 * self.outbox
        inserted = 0
        for slot, kind, dst, delay, payload in sorted(sends,
                                                      key=lambda s: s[0]):
            if delay is None:
                x = self.rng.at(base + 2 * slot)
                lat = self.lat_min + x % (self.lat_max - self.lat_min)
                u = (self.rng.at(base + 2 * slot + 1) >> 8) * 2.0 ** -24
                clogged = self.clog_node[src] or self.clog_node[dst] \
                    or self.clog_link[src][dst]
                if clogged or u < self.loss:
                    continue
                ev = Event(self.now + min(lat, INF - self.now), kind, 0,
                           src, dst, self.gen[dst] % 256, payload)
            else:
                ev = Event(self.now + min(max(delay, 0), INF - self.now),
                           kind, TIMER, src, dst, self.gen[dst] % 256,
                           payload)
            inserted += self._push(ev)
        return inserted

    # -- faults ------------------------------------------------------------
    def _fault(self, op: int, a: int, b: int):
        if op == KILL:
            self.alive[a] = False
            self.gen[a] += 1
        elif op == RESTART:
            self.alive[a] = True
            self.gen[a] += 1
            return self._restart(a)
        elif op in (CLOG_NODE, UNCLOG_NODE):
            self.clog_node[a] = op == CLOG_NODE
        else:
            self.clog_link[a][b] = op == CLOG_LINK
        return []

    def _restart(self, me: int):
        """Crash recovery: term, vote, snapshot and log persist; the
        server has applied exactly its snapshot; the rest resets."""
        n, sv = self.n, self.s[me]
        if self.control == "restart_drops_snapshot":
            sv.snap = sv.snap_term = sv.snap_digest = 0
            sv.log = []
        sv.role, sv.votes = FOLLOWER, 0
        sv.commit, sv.applied_digest = sv.snap, sv.snap_digest
        sv.next_idx, sv.match_idx = [1] * n, [0] * n
        sv.epoch += 1
        d = self._election_delay()
        sv.timeout, sv.deadline = d, self.now + d
        sends = [(n, ELECTION, me, d, [sv.epoch])]
        k = max(0, -((self.cmd_start - self.now) // self.cmd_gap))
        if k < self.n_cmds:
            sends.append((n + 1, PROPOSE, me,
                          self.cmd_start + k * self.cmd_gap - self.now,
                          [k + 1]))
        return sends

    # -- Raft --------------------------------------------------------------
    def _message_to(self, me: int, j: int):
        """The leader's next message to peer ``j``: the snapshot if it
        covers ``next_idx``, else AppendEntries with at most one entry;
        ``next_idx`` moves past what is sent."""
        sv = self.s[me]
        nxt = max(sv.next_idx[j], 1)
        if nxt <= sv.snap:
            sv.next_idx[j] = sv.snap + 1
            return (j, INSTALL, j, None,
                    [sv.term, me, sv.snap, sv.snap_term, sv.snap_digest,
                     sv.commit])
        have = nxt <= sv.last()
        e_term, e_cmd = sv.entry(nxt) if have else (0, 0)
        sv.next_idx[j] = nxt + int(have)
        return (j, APPEND, j, None,
                [sv.term, me, nxt - 1, sv.term_at(nxt - 1), int(have),
                 e_term, e_cmd, sv.commit])

    def _apply(self, me: int, commit0: int, digest0: int) -> None:
        """Apply the entries committed by this event (``commit0`` and
        ``digest0`` are the commit index and applied digest before it),
        and compact if the commit index has crossed a multiple of the
        interval."""
        sv = self.s[me]

        def applied(k):
            d = digest0
            for i in range(commit0 + 1, k + 1):
                d += entry_hash(i, sv.entry(i)[1])
            return d & M32

        sv.applied_digest = applied(sv.commit)
        cut = sv.commit // self.interval * self.interval
        if cut > sv.snap:
            sv.snap_term = sv.term_at(cut)
            sv.snap_digest = applied(cut)
            sv.log = sv.log[cut - sv.snap:]
            sv.snap = cut
            self.snapshots += 1

    def _handle(self, ev: Event, me: int):
        """Deliver ``ev`` to server ``me``: the event's own effects, then
        what it committed is applied, then the leader's messages are
        built from the state after both."""
        sv = self.s[me]
        commit0, digest0 = sv.commit, sv.applied_digest
        sends, peers, bug, installed = self._event(ev, me)
        if not installed:
            self._apply(me, commit0, digest0)
        return sends + [self._message_to(me, j) for j in peers], bug

    def _event(self, ev: Event, me: int):
        """The event's own effects: returns (replies and timers, peers the
        leader sends its next message to, election-safety bug, whether a
        snapshot was installed)."""
        n, L = self.n, self.L
        sv = self.s[me]
        peers = [j for j in range(n) if j != me]
        kind, p = ev.kind, ev.payload + [0] * (8 - len(ev.payload))
        t = p[0]
        if kind in (REQVOTE, VOTEREPLY, APPEND, APPENDREPLY, INSTALL):
            if t > sv.term:
                sv.term, sv.voted_for, sv.role = t, -1, FOLLOWER
            elif kind in (APPEND, INSTALL) and t == sv.term \
                    and sv.role == CANDIDATE:
                sv.role = FOLLOWER
        timer_slot = n
        if kind == ELECTION:
            if p[0] != sv.epoch:
                return [], [], False, False
            sends = []
            if self.now >= sv.deadline:
                if sv.role != LEADER:
                    sv.timeout = self._election_delay()
                    sv.term += 1
                    sv.voted_for, sv.role, sv.votes = me, CANDIDATE, 1 << me
                    sends = [(j, REQVOTE, j, None,
                              [sv.term, me, sv.last(),
                               sv.term_at(sv.last())])
                             for j in range(n) if j != me]
                sv.deadline = self.now + sv.timeout
            return sends + [(timer_slot, ELECTION, me,
                             sv.deadline - self.now, [sv.epoch])], \
                [], False, False
        if kind == HEARTBEAT:
            if sv.role != LEADER or sv.term != p[0]:
                return [], [], False, False
            return [(timer_slot, HEARTBEAT, me, self.heartbeat, [p[0]])], \
                peers, False, False
        if kind == REQVOTE:
            cand = min(max(p[1], 0), n - 1)
            last = sv.term_at(sv.last())
            up_to_date = p[3] > last or (p[3] == last and p[2] >= sv.last())
            can_vote = self.double_vote or sv.voted_for in (-1, cand)
            grant = t >= sv.term and up_to_date and can_vote
            if grant:
                sv.voted_for = cand
                sv.deadline = self.now + sv.timeout
            return [(cand, VOTEREPLY, cand, None,
                     [sv.term, int(grant), me, 0])], [], False, False
        if kind == VOTEREPLY:
            if not (p[1] != 0 and sv.role == CANDIDATE and t == sv.term):
                return [], [], False, False
            sv.votes |= 1 << min(max(p[2], 0), n - 1)
            if bin(sv.votes).count("1") <= n // 2:
                return [], [], False, False
            term = min(sv.term, WON_BITS - 1)
            bug = any(term in self.s[j].won for j in range(n) if j != me)
            sv.won.add(term)
            sv.role = LEADER
            sv.match_idx = [sv.last() if j == me else 0 for j in range(n)]
            sv.next_idx = [1 + sv.last()] * n
            self.first_leader = min(self.first_leader, self.now)
            self.elections_won += 1
            return [(timer_slot, HEARTBEAT, me, self.heartbeat,
                     [sv.term])], peers, bug, False
        if kind == APPEND:
            leader = min(max(p[1], 0), n - 1)
            prev_idx, prev_term, n_ent, e_term, e_cmd, l_commit = p[2:8]
            if t < sv.term:
                return [(leader, APPENDREPLY, leader, None,
                         [sv.term, 0, 0, me])], [], False, False
            sv.deadline = self.now + sv.timeout
            behind = prev_idx < sv.snap
            if not behind and not (prev_idx <= sv.last()
                                   and sv.term_at(prev_idx) == prev_term):
                hint = min(sv.last(), prev_idx - 1)
                return [(leader, APPENDREPLY, leader, None,
                         [sv.term, 0, hint, me])], [], False, False
            idx = prev_idx + 1
            match = sv.snap if behind else prev_idx
            if not behind and n_ent > 0 and idx - sv.snap <= L:
                if not (idx <= sv.last()
                        and sv.entry(idx) == (e_term, e_cmd)):
                    sv.log = sv.log[:idx - sv.snap - 1] + [(e_term, e_cmd)]
                match = idx
            # Figure 2: commit up to the last entry this message vouched for.
            sv.commit = max(sv.commit, min(l_commit, match))
            return [(leader, APPENDREPLY, leader, None,
                     [sv.term, 1, match, me])], [], False, False
        if kind == INSTALL:
            self.installs += 1
            leader = min(max(p[1], 0), n - 1)
            last_idx, last_term, digest = p[2], p[3], p[4]
            if t < sv.term:
                return [(leader, APPENDREPLY, leader, None,
                         [sv.term, 0, 0, me])], [], False, False
            sv.deadline = self.now + sv.timeout
            installed = last_idx > sv.commit
            if installed:
                if last_idx <= sv.last() and sv.term_at(last_idx) == last_term:
                    sv.log = sv.log[last_idx - sv.snap:]
                else:
                    sv.log = []
                sv.snap, sv.snap_term, sv.snap_digest = \
                    last_idx, last_term, digest
                sv.commit, sv.applied_digest = last_idx, digest
            return [(leader, APPENDREPLY, leader, None,
                     [sv.term, 1, last_idx, me])], [], False, installed
        if kind == APPENDREPLY:
            if sv.role != LEADER or t != sv.term:
                return [], [], False, False
            f = min(max(p[3], 0), n - 1)
            cur_match, cur_next = sv.match_idx[f], sv.next_idx[f]
            if p[1] != 0:
                sv.match_idx[f] = max(cur_match, p[2])
                sv.next_idx[f] = max(cur_next, sv.match_idx[f] + 1)
                catch = p[2] > cur_match and sv.next_idx[f] <= sv.last()
            else:
                sv.next_idx[f] = max(1, min(cur_next - 1, p[2] + 1))
                catch = True
            for k in range(sv.last(), sv.snap, -1):
                if sv.term_at(k) == sv.term \
                        and sum(m >= k for m in sv.match_idx) > n // 2:
                    sv.commit = max(sv.commit, k)
                    break
            return [], [f] if catch else [], False, False
        if kind == PROPOSE:
            sends = []
            if p[0] < self.n_cmds:
                sends.append((n + 1, PROPOSE, me, self.cmd_gap, [p[0] + 1]))
            if sv.role != LEADER or sv.last() - sv.snap >= L:
                return sends, [], False, False
            sv.log.append((sv.term, p[0]))
            sv.match_idx[me] = sv.last()
            return sends, peers, False, False
        raise ValueError(f"unknown event kind {kind}")

    def _unsafe(self) -> bool:
        """The safety checks that run after every step."""
        s = self.s
        if any(sv.commit > sv.last() for sv in s):
            return True
        for i in range(self.n):
            for j in range(i + 1, self.n):
                lo = max(s[i].snap, s[j].snap)
                hi = min(s[i].commit, s[j].commit)
                if hi < lo:
                    continue
                for k in range(lo + 1, hi + 1):
                    if s[i].entry(k) != s[j].entry(k):
                        return True
                if s[i].digest(hi) != s[j].digest(hi):
                    return True
        return False

    # -- what the sweep reports per seed -----------------------------------
    def row(self) -> dict:
        return {
            "now_us": self.now, "active": self.active, "steps": self.steps,
            "delivered": self.delivered, "dropped": self.dropped,
            "overflow": self.overflow, "qmax": self.qmax, "bug": self.bug,
            "bug_time_us": self.bug_time, "queue_depth": self.depth,
            "leader_elected": self.first_leader < INF,
            "first_leader_time_us": self.first_leader,
            "elections_won": self.elections_won,
            "max_commit": max(sv.commit for sv in self.s),
            "max_term": max(sv.term for sv in self.s),
        }


def reference_row(seed: int, engine: dict, raft: dict, faults=(),
                  steps: int | None = None, control: str | None = None):
    """The row a world of ``seed`` reports once finished, or after
    ``steps`` steps if it is still live then."""
    return World(seed, engine, raft, faults, control).run(steps).row()
