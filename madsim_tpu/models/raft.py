"""A MadRaft-equivalent: Raft consensus written against the framework API.

The reference ecosystem's flagship workload is MadRaft (an external repo built
on madsim; referenced at `README.md` of the reference). This module plays the
same role for madsim_tpu: leader election + log replication + crash-safe
persistence (via the simulated fs) + invariant checking, exercising endpoints,
RPC, timers, node kill/restart, and partitions. It is the payload for the
BASELINE.md benchmark configs (3-node election, 5-node replication sweeps).

This is the *host-engine* implementation (arbitrary Python, one seed per run).
The batched device engine has its own pure-JAX Raft actor in
``madsim_tpu.engine.raft_actor`` for the vmapped seed sweeps.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import madsim_tpu as ms
from madsim_tpu import fs, rand, task, time
from madsim_tpu.net import Endpoint
from madsim_tpu.net import rpc as msrpc

# ---------------------------------------------------------------------------
# Messages (in-sim these cross the network as objects, zero serialization)
# ---------------------------------------------------------------------------


@dataclass
class RequestVote:
    term: int
    candidate: int
    last_log_index: int
    last_log_term: int


@dataclass
class VoteReply:
    term: int
    granted: bool


@dataclass
class AppendEntries:
    term: int
    leader: int
    prev_index: int
    prev_term: int
    entries: List[Tuple[int, Any]]  # (term, command)
    leader_commit: int


@dataclass
class AppendReply:
    term: int
    success: bool
    match_index: int


FOLLOWER, CANDIDATE, LEADER = "follower", "candidate", "leader"


class RaftInvariantViolation(AssertionError):
    """Election safety / log matching violated — the 'bug flag' of the sim."""


class InvariantChecker:
    """Cross-node white-box checker (a simulation superpower: all nodes are
    in-process, so safety properties are asserted globally and instantly)."""

    def __init__(self):
        self.leaders_by_term: Dict[int, int] = {}
        self.committed: List[Tuple[int, Any]] = []  # longest committed prefix

    def on_become_leader(self, node: int, term: int) -> None:
        prev = self.leaders_by_term.setdefault(term, node)
        if prev != node:
            raise RaftInvariantViolation(
                f"election safety violated: term {term} has leaders {prev} and {node}"
            )

    def on_commit(self, node: int, log: List[Tuple[int, Any]], commit_index: int) -> None:
        prefix = log[:commit_index]
        n = min(len(prefix), len(self.committed))
        if prefix[:n] != self.committed[:n]:
            raise RaftInvariantViolation(
                f"log matching violated at node {node}: committed prefixes diverge"
            )
        if len(prefix) > len(self.committed):
            self.committed = list(prefix)


@dataclass
class RaftOptions:
    election_timeout: Tuple[float, float] = (0.15, 0.30)  # seconds, randomized
    heartbeat_interval: float = 0.05
    rpc_timeout: float = 0.10
    port: int = 7000
    persist: bool = True  # durable term/vote/log via the simulated fs
    # Injected bug (same switch as engine/raft_actor.py RaftDeviceConfig):
    # grant votes ignoring the one-vote-per-term rule, so seed sweeps have a
    # real election-safety violation to find. Used by the host↔device
    # cross-validation (tests/test_crossvalidation.py).
    buggy_double_vote: bool = False


class RaftServer:
    """One Raft peer. Runs as a node's init task; survives crash-restart by
    reloading persistent state from the simulated disk."""

    def __init__(self, me: int, peers: List[str], checker: InvariantChecker,
                 opts: RaftOptions):
        self.me = me
        self.peers = peers  # ip strings, index == node index
        self.checker = checker
        self.opts = opts
        # Persistent state
        self.term = 0
        self.voted_for: Optional[int] = None
        self.log: List[Tuple[int, Any]] = []  # 1-based indexing helpers below
        # Volatile
        self.role = FOLLOWER
        self.commit_index = 0
        self.last_applied = 0
        self.applied: List[Any] = []
        self.leader_hint: Optional[int] = None
        self._last_heartbeat = 0.0
        self._ep: Optional[Endpoint] = None
        self._node: Optional[ms.NodeHandle] = None  # set in serve()
        # Leader volatile
        self.next_index: Dict[int, int] = {}
        self.match_index: Dict[int, int] = {}

    # -- log helpers (1-based) ---------------------------------------------
    def last_log_index(self) -> int:
        return len(self.log)

    def log_term(self, index: int) -> int:
        if index == 0:
            return 0
        return self.log[index - 1][0]

    # -- persistence --------------------------------------------------------
    async def _persist(self) -> None:
        if not self.opts.persist:
            return
        import pickle

        blob = pickle.dumps((self.term, self.voted_for, self.log))
        f = await fs.File.open_or_create("/raft-state")
        await f.set_len(0)
        await f.write_all_at(blob, 0)
        await f.sync_all()

    async def _restore(self) -> None:
        if not self.opts.persist:
            return
        import pickle

        try:
            blob = await fs.read("/raft-state")
        except FileNotFoundError:
            return
        if blob:
            self.term, self.voted_for, self.log = pickle.loads(blob)

    # -- role transitions ----------------------------------------------------
    async def _become_follower(self, term: int) -> None:
        self.role = FOLLOWER
        if term > self.term:
            self.term = term
            self.voted_for = None
            await self._persist()

    async def _become_leader(self) -> None:
        self.role = LEADER
        self.checker.on_become_leader(self.me, self.term)
        n = self.last_log_index() + 1
        self.next_index = {i: n for i in range(len(self.peers))}
        self.match_index = {i: 0 for i in range(len(self.peers))}
        self.match_index[self.me] = self.last_log_index()
        task.spawn(self._heartbeat_loop(self.term))

    # -- main ---------------------------------------------------------------
    async def serve(self) -> None:
        self._node = task.current_node()
        await self._restore()
        self._ep = await Endpoint.bind((self.peers[self.me], self.opts.port))
        msrpc.add_rpc_handler(self._ep, RequestVote, self._on_request_vote)
        msrpc.add_rpc_handler(self._ep, AppendEntries, self._on_append_entries)
        self._last_heartbeat = time.monotonic()
        await self._election_loop()

    async def _election_loop(self) -> None:
        while True:
            timeout = rand.thread_rng().gen_range_f64(*self.opts.election_timeout)
            await time.sleep(timeout)
            if self.role == LEADER:
                continue
            if time.monotonic() - self._last_heartbeat < timeout:
                continue
            await self._start_election()

    async def _start_election(self) -> None:
        self.role = CANDIDATE
        self.term += 1
        self.voted_for = self.me
        await self._persist()
        term = self.term
        votes = [self.me]
        won = ms.sync.Event()

        async def ask(peer: int):
            req = RequestVote(term, self.me, self.last_log_index(),
                              self.log_term(self.last_log_index()))
            try:
                reply = await msrpc.call(self._ep, (self.peers[peer], self.opts.port),
                                         req, timeout=self.opts.rpc_timeout)
            except (TimeoutError, OSError):
                return
            if reply.term > self.term:
                await self._become_follower(reply.term)
                return
            if self.role == CANDIDATE and self.term == term and reply.granted:
                votes.append(peer)
                if len(votes) > len(self.peers) // 2:
                    won.set()

        for peer in range(len(self.peers)):
            if peer != self.me:
                task.spawn(ask(peer))
        try:
            await time.timeout(self.opts.election_timeout[0], won.wait())
        except TimeoutError:
            return  # election failed; loop will retry with a new timeout
        if self.role == CANDIDATE and self.term == term:
            await self._become_leader()

    async def _heartbeat_loop(self, term: int) -> None:
        while self.role == LEADER and self.term == term:
            for peer in range(len(self.peers)):
                if peer != self.me:
                    task.spawn(self._replicate_to(peer, term))
            await time.sleep(self.opts.heartbeat_interval)

    async def _replicate_to(self, peer: int, term: int) -> None:
        if self.role != LEADER or self.term != term:
            return
        next_i = self.next_index[peer]
        prev_index = next_i - 1
        entries = list(self.log[next_i - 1:])
        req = AppendEntries(term, self.me, prev_index, self.log_term(prev_index),
                            entries, self.commit_index)
        try:
            reply = await msrpc.call(self._ep, (self.peers[peer], self.opts.port),
                                     req, timeout=self.opts.rpc_timeout)
        except (TimeoutError, OSError):
            return
        if reply.term > self.term:
            await self._become_follower(reply.term)
            return
        if self.role != LEADER or self.term != term:
            return
        if reply.success:
            self.match_index[peer] = max(self.match_index[peer], reply.match_index)
            self.next_index[peer] = self.match_index[peer] + 1
            self._advance_commit()
        else:
            self.next_index[peer] = max(1, self.next_index[peer] - 1)

    def _advance_commit(self) -> None:
        for n in range(self.last_log_index(), self.commit_index, -1):
            if self.log_term(n) != self.term:
                continue
            count = sum(1 for i in range(len(self.peers)) if self.match_index.get(i, 0) >= n)
            if count > len(self.peers) // 2:
                self.commit_index = n
                self._apply()
                break

    def _apply(self) -> None:
        self.checker.on_commit(self.me, self.log, self.commit_index)
        while self.last_applied < self.commit_index:
            self.last_applied += 1
            self.applied.append(self.log[self.last_applied - 1][1])

    # -- RPC handlers --------------------------------------------------------
    async def _on_request_vote(self, req: RequestVote) -> VoteReply:
        if req.term > self.term:
            await self._become_follower(req.term)
        if req.term < self.term:
            return VoteReply(self.term, False)
        up_to_date = (req.last_log_term, req.last_log_index) >= (
            self.log_term(self.last_log_index()), self.last_log_index())
        can_vote = (True if self.opts.buggy_double_vote
                    else self.voted_for in (None, req.candidate))
        if up_to_date and can_vote:
            self.voted_for = req.candidate
            await self._persist()
            self._last_heartbeat = time.monotonic()
            return VoteReply(self.term, True)
        return VoteReply(self.term, False)

    async def _on_append_entries(self, req: AppendEntries) -> AppendReply:
        if req.term > self.term or (req.term == self.term and self.role == CANDIDATE):
            await self._become_follower(req.term)
        if req.term < self.term:
            return AppendReply(self.term, False, 0)
        self._last_heartbeat = time.monotonic()
        self.leader_hint = req.leader
        if req.prev_index > self.last_log_index() or \
                self.log_term(req.prev_index) != req.prev_term:
            return AppendReply(self.term, False, 0)
        # Append / overwrite conflicting suffix
        changed = False
        for k, entry in enumerate(req.entries):
            idx = req.prev_index + 1 + k
            if idx <= self.last_log_index():
                if self.log[idx - 1] != entry:
                    del self.log[idx - 1:]
                    self.log.append(entry)
                    changed = True
            else:
                self.log.append(entry)
                changed = True
        if changed:
            await self._persist()
        if req.leader_commit > self.commit_index:
            self.commit_index = min(req.leader_commit, self.last_log_index())
            self._apply()
        return AppendReply(self.term, True, req.prev_index + len(req.entries))

    # -- client interface ----------------------------------------------------
    def start(self, command: Any) -> Optional[Tuple[int, int]]:
        """Leader-side propose: append to local log → (index, term), or None
        if this server is not the leader."""
        if self.role != LEADER:
            return None
        self.log.append((self.term, command))
        self.match_index[self.me] = self.last_log_index()
        # Spawn on *this server's* node: persistence must hit this node's
        # disk and replication tasks must die with this node, even when
        # start() is called from a client/supervisor task elsewhere.
        self._node.spawn(self._persist())
        term = self.term
        for peer in range(len(self.peers)):
            if peer != self.me:
                self._node.spawn(self._replicate_to(peer, term))
        return self.last_log_index(), self.term


class RaftCluster:
    """N Raft peers as simulated nodes, plus chaos/observation helpers."""

    def __init__(self, n: int, opts: Optional[RaftOptions] = None,
                 ip_prefix: str = "10.0.1."):
        self.n = n
        self.opts = opts or RaftOptions()
        self.checker = InvariantChecker()
        self.ips = [f"{ip_prefix}{i + 1}" for i in range(n)]
        self.servers: Dict[int, RaftServer] = {}
        self.nodes: List[ms.NodeHandle] = []
        handle = ms.Handle.current()
        for i in range(n):
            self.nodes.append(handle.create_node(
                name=f"raft-{i}", ip=self.ips[i], init=self._make_init(i)))

    def _make_init(self, i: int):
        async def init():
            server = RaftServer(i, self.ips, self.checker, self.opts)
            self.servers[i] = server
            await server.serve()

        return init

    # -- observation --------------------------------------------------------
    def leader(self) -> Optional[int]:
        leaders = [i for i, s in self.servers.items()
                   if s.role == LEADER and not self._is_killed(i)]
        if not leaders:
            return None
        # Highest term wins (stale leaders may linger across partitions).
        return max(leaders, key=lambda i: self.servers[i].term)

    def _is_killed(self, i: int) -> bool:
        return not self.nodes[i].is_alive()

    async def wait_for_leader(self, timeout: float = 10.0) -> int:
        async def waiter():
            while True:
                lead = self.leader()
                if lead is not None:
                    return lead
                await time.sleep(0.01)

        return await time.timeout(timeout, waiter())

    async def propose(self, command: Any, timeout: float = 10.0) -> Tuple[int, int]:
        """Find the leader, propose, and wait for commit."""

        async def attempt():
            while True:
                lead = self.leader()
                if lead is None:
                    await time.sleep(0.02)
                    continue
                # kill()/restart() pop the server entry; a concurrent kill
                # during any of the sleeps below must read as "leadership
                # lost: retry", never KeyError.
                server = self.servers.get(lead)
                if server is None:
                    await time.sleep(0.02)
                    continue
                started = server.start(command)
                if started is None:
                    await time.sleep(0.02)
                    continue
                index, term = started
                while True:
                    server = self.servers.get(lead)
                    if server is None:
                        break  # leader killed mid-commit: retry from scratch
                    if server.commit_index >= index and \
                            server.last_log_index() >= index and \
                            server.log_term(index) == term:
                        return index, term
                    if server.role != LEADER or server.term != term or self._is_killed(lead):
                        break  # leadership lost: retry from scratch
                    await time.sleep(0.01)

        return await time.timeout(timeout, attempt())

    # -- chaos --------------------------------------------------------------
    def kill(self, i: int) -> None:
        ms.Handle.current().kill(self.nodes[i])
        # Drop the orphaned server object immediately: observers must not act
        # on it between kill and the respawned init re-registering.
        self.servers.pop(i, None)

    def restart(self, i: int) -> None:
        ms.Handle.current().restart(self.nodes[i])
        # The replacement registers itself when the init task runs; until
        # then no server for i must be visible to leader()/propose().
        self.servers.pop(i, None)

    def partition(self, group_a: List[int], group_b: List[int]) -> None:
        from madsim_tpu.net import NetSim

        sim = ms.simulator(NetSim)
        for a in group_a:
            for b in group_b:
                sim.disconnect2(self.nodes[a].id, self.nodes[b].id)

    def heal(self) -> None:
        from madsim_tpu.net import NetSim

        sim = ms.simulator(NetSim)
        for a in range(self.n):
            for b in range(self.n):
                if a != b:
                    sim.connect2(self.nodes[a].id, self.nodes[b].id)
