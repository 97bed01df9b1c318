"""Plain reference of the Raft deployments this benchmark runs.

One world at a time, in plain Python integers: the discrete-event loop
(earliest pending event first; equal times in slot order; freed slots
reused lowest first), the network model (per-message latency drawn at
send time, link clogs), crash faults, and Raft's election and log
replication as the MadRaft labs and Figure 2 of the Raft paper state
them. Every random decision draws from a counter-based Threefry-2x32
stream keyed by the world's seed, so the same seed gives the same world.

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
ELECTION, HEARTBEAT, REQVOTE, VOTEREPLY, APPEND, APPENDREPLY, PROPOSE = \
    range(7)
TIMER, FAULT = 1, 2               # event flags
KILL, RESTART, CLOG_NODE, UNCLOG_NODE, CLOG_LINK, UNCLOG_LINK = range(6)
WON_BITS = 128                    # terms tracked for election safety

# The one guarantee the control breaks: a server that grants a vote
# restarts its election timeout (Raft paper, Figure 2, rules for
# followers).
CONTROLS = ("grant_keeps_election_timer",)

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


class World:
    """One seeded simulation of a Raft cluster.

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
        self.qcap = int(engine["queue_cap"])
        self.t_limit = int(engine["t_limit_us"])
        self.lat_min = int(engine["latency_min_us"])
        self.lat_max = int(engine["latency_max_us"])
        self.loss = float(engine["loss_rate"])
        self.stop_on_bug = bool(engine["stop_on_bug"])
        self.outbox = n + 1                 # n peer messages + one timer
        self.elect = (int(raft["elect_min_us"]), int(raft["elect_max_us"]))
        self.heartbeat = int(raft["heartbeat_us"])
        self.double_vote = bool(raft["buggy_double_vote"])
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

        L = self.L
        self.term = [0] * n
        self.voted_for = [-1] * n
        self.role = [FOLLOWER] * n
        self.votes = [0] * n
        self.commit = [0] * n
        self.log_len = [0] * n
        self.log_term = [[0] * L for _ in range(n)]
        self.log_cmd = [[0] * L for _ in range(n)]
        self.next_idx = [[1] * n for _ in range(n)]
        self.match_idx = [[0] * n for _ in range(n)]
        self.epoch = [0] * n
        self.first_leader = INF
        self.elections_won = 0
        self.won = [set() for _ in range(n)]

        init = []
        for i in range(n):
            init.append(Event(self._election_delay(), ELECTION, TIMER,
                              i, i, 0, [0]))
        for p in range(int(raft["n_proposals"])):
            t = int(raft["propose_start_us"]) \
                + p * int(raft["propose_interval_us"])
            for i in range(n):
                init.append(Event(t, PROPOSE, 0, i, i, 0, [p + 1]))
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
        if (delivered and hbug) or self._committed_logs_disagree():
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
        """Crash recovery: term, vote and log persist; the rest resets."""
        n = self.n
        self.role[me] = FOLLOWER
        self.votes[me] = 0
        self.commit[me] = 0
        self.next_idx[me] = [1] * n
        self.match_idx[me] = [0] * n
        self.epoch[me] += 1
        return [(n, ELECTION, me, self._election_delay(), [self.epoch[me]])]

    # -- Raft --------------------------------------------------------------
    def _last_term(self, me: int, idx: int) -> int:
        if idx <= 0:
            return 0
        return self.log_term[me][min(idx, self.L) - 1]

    def _appends(self, me: int):
        """AppendEntries (at most one entry) to every peer."""
        out = []
        for j in range(self.n):
            if j == me:
                continue
            nxt = min(max(self.next_idx[me][j], 1), self.L + 1)
            have = nxt <= self.log_len[me]
            pos = min(nxt, self.L) - 1
            out.append((j, APPEND, j, None, [
                self.term[me], me, nxt - 1, self._last_term(me, nxt - 1),
                int(have), self.log_term[me][pos] if have else 0,
                self.log_cmd[me][pos] if have else 0, self.commit[me]]))
        return out

    def _handle(self, ev: Event, me: int):
        n, L = self.n, self.L
        kind, p = ev.kind, ev.payload + [0] * (8 - len(ev.payload))
        t = p[0]
        if kind in (REQVOTE, VOTEREPLY, APPEND, APPENDREPLY):
            if t > self.term[me]:
                self.term[me], self.voted_for[me] = t, -1
                self.role[me] = FOLLOWER
            elif kind == APPEND and t == self.term[me] \
                    and self.role[me] == CANDIDATE:
                self.role[me] = FOLLOWER
        timer_slot = n
        if kind == ELECTION:
            delay = self._election_delay()
            if p[0] != self.epoch[me]:
                return [], False
            sends = [(timer_slot, ELECTION, me, delay, [self.epoch[me]])]
            if self.role[me] != LEADER:
                self.term[me] += 1
                self.voted_for[me] = me
                self.role[me] = CANDIDATE
                self.votes[me] = 1 << me
                sends += [(j, REQVOTE, j, None,
                           [self.term[me], me, self.log_len[me],
                            self._last_term(me, self.log_len[me])])
                          for j in range(n) if j != me]
            return sends, False
        if kind == HEARTBEAT:
            if self.role[me] != LEADER or self.term[me] != p[0]:
                return [], False
            return self._appends(me) + [
                (timer_slot, HEARTBEAT, me, self.heartbeat, [p[0]])], False
        if kind == REQVOTE:
            delay = self._election_delay()
            cand = min(max(p[1], 0), n - 1)
            last = self._last_term(me, self.log_len[me])
            up_to_date = p[3] > last or (p[3] == last
                                         and p[2] >= self.log_len[me])
            can_vote = self.double_vote or self.voted_for[me] in (-1, cand)
            grant = t >= self.term[me] and up_to_date and can_vote
            sends = [(cand, VOTEREPLY, cand, None,
                      [self.term[me], int(grant), me, 0])]
            if grant:
                self.voted_for[me] = cand
                if self.control != "grant_keeps_election_timer":
                    self.epoch[me] += 1
                    sends.append((timer_slot, ELECTION, me, delay,
                                  [self.epoch[me]]))
            return sends, False
        if kind == VOTEREPLY:
            if not (p[1] != 0 and self.role[me] == CANDIDATE
                    and t == self.term[me]):
                return [], False
            self.votes[me] |= 1 << min(max(p[2], 0), n - 1)
            if bin(self.votes[me]).count("1") <= n // 2:
                return [], False
            term = min(self.term[me], WON_BITS - 1)
            bug = any(term in self.won[j] for j in range(n) if j != me)
            self.won[me].add(term)
            self.role[me] = LEADER
            self.match_idx[me] = [self.log_len[me] if j == me else 0
                                  for j in range(n)]
            self.next_idx[me] = [1 + self.log_len[me]] * n
            self.first_leader = min(self.first_leader, self.now)
            self.elections_won += 1
            return self._appends(me) + [
                (timer_slot, HEARTBEAT, me, self.heartbeat,
                 [self.term[me]])], bug
        if kind == APPEND:
            delay = self._election_delay()
            leader = min(max(p[1], 0), n - 1)
            prev_idx, prev_term, n_ent, e_term, e_cmd, l_commit = p[2:8]
            if t < self.term[me]:
                return [(leader, APPENDREPLY, leader, None,
                         [self.term[me], 0, 0, me])], False
            llen = self.log_len[me]
            ok = prev_idx <= llen \
                and self._last_term(me, prev_idx) == prev_term
            match = 0
            if ok:
                match = prev_idx
                idx = prev_idx + 1
                if n_ent > 0 and idx <= L:
                    same = idx <= llen \
                        and self.log_term[me][idx - 1] == e_term \
                        and self.log_cmd[me][idx - 1] == e_cmd
                    self.log_term[me][idx - 1] = e_term
                    self.log_cmd[me][idx - 1] = e_cmd
                    if not same:
                        self.log_len[me] = idx
                    match = idx
                self.commit[me] = max(self.commit[me],
                                      min(l_commit, self.log_len[me]))
            self.epoch[me] += 1
            return [(leader, APPENDREPLY, leader, None,
                     [self.term[me], int(ok), match, me]),
                    (timer_slot, ELECTION, me, delay,
                     [self.epoch[me]])], False
        if kind == APPENDREPLY:
            if self.role[me] != LEADER or t != self.term[me]:
                return [], False
            f = min(max(p[3], 0), n - 1)
            if p[1] != 0:
                self.match_idx[me][f] = max(self.match_idx[me][f], p[2])
                self.next_idx[me][f] = self.match_idx[me][f] + 1
            else:
                self.next_idx[me][f] = max(1, self.next_idx[me][f] - 1)
            for k in range(self.log_len[me], 0, -1):
                if k <= L and self.log_term[me][k - 1] == self.term[me] \
                        and sum(m >= k for m in self.match_idx[me]) > n // 2:
                    self.commit[me] = max(self.commit[me], k)
                    break
            return [], False
        if kind == PROPOSE:
            if self.role[me] != LEADER or self.log_len[me] >= L:
                return [], False
            k = self.log_len[me]
            self.log_term[me][k] = self.term[me]
            self.log_cmd[me][k] = p[0]
            self.log_len[me] = k + 1
            self.match_idx[me][me] = k + 1
            return self._appends(me), False
        raise ValueError(f"unknown event kind {kind}")

    def _committed_logs_disagree(self) -> bool:
        """Log matching on committed prefixes (Raft §5.3)."""
        for i in range(self.n):
            for j in range(i + 1, self.n):
                for k in range(min(self.commit[i], self.commit[j], self.L)):
                    if self.log_term[i][k] != self.log_term[j][k] \
                            or self.log_cmd[i][k] != self.log_cmd[j][k]:
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
            "max_commit": max(self.commit), "max_term": max(self.term),
        }


def reference_row(seed: int, engine: dict, raft: dict, faults=(),
                  steps: int | None = None, control: str | None = None):
    """The row a world of ``seed`` reports once finished, or after
    ``steps`` steps if it is still live then."""
    return World(seed, engine, raft, faults, control).run(steps).row()
