"""madsim_tpu — a TPU-native deterministic simulation testing framework.

Capabilities of madsim (the Rust Magical Deterministic Simulator): seeded,
bit-reproducible discrete-event simulation of distributed systems — virtual
time, a simulated network with latency/loss/partition fault injection, node
kill/restart/pause, deterministic RNG, drop-in shims for real async/RPC APIs,
and a multi-seed test harness with a determinism checker.

TPU-native architecture: the host engine (this package's ``core``/``net``)
runs arbitrary Python coroutines one seed at a time; the batched device
engine (``engine``) lifts the decision kernel — next-event selection,
virtual-clock advance, RNG draws, link sampling, fault schedules — into a JAX
step function vmapped over thousands of seeds and sharded across a TPU mesh
(``parallel``). Both draw from the same counter-based Threefry stream
(``ops.threefry``), so randomness is a pure function of (seed, stream, index)
on every backend.
"""
# The one compile-cache rule (parallel/compile_cache.py), applied at
# package import, before anything compiles (jax latches its cache at the
# first compile), so every entry point — benchmark/, chip_smoke, tools/,
# fleet worker processes — follows it. Loaded by file path, NOT `from
# .parallel import ...`: the parallel package init imports jax and the
# engine, and the host-only import path stays jax-free.
import os as _os
from importlib import util as _ilu

_spec = _ilu.spec_from_file_location(
    "madsim_tpu._compile_cache_boot",
    _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                  "parallel", "compile_cache.py"))
_cc = _ilu.module_from_spec(_spec)
_spec.loader.exec_module(_cc)
_cc.apply()

from .core.config import Config, FsConfig, NetConfig, TcpConfig
from .core.context import NoRuntimeError
from .core.futures import Cancelled, ChannelClosed
from .core.rng import DeterminismError
from .core.runtime import Handle, NodeHandle, Runtime, init_logger
from .core.task import Deadlock, JoinHandle, TimeLimitExceeded
from .core.plugin import Simulator, simulator

from .testing import Builder, main, run, test

from . import fs, net, rand, sync, task, time

__version__ = "0.1.0"

__all__ = [
    "Config", "NetConfig", "TcpConfig", "FsConfig",
    "Runtime", "Handle", "NodeHandle", "init_logger",
    "Deadlock", "TimeLimitExceeded", "DeterminismError", "NoRuntimeError",
    "Cancelled", "ChannelClosed",
    "Builder", "main", "run", "test",
    "Simulator", "simulator",
    "fs", "net", "rand", "sync", "task", "time",
]
