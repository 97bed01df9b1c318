"""The benchmark's own tests run on the CPU, at small sizes: JAX reads
``JAX_PLATFORMS`` at import, so it is set before any test imports it."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

# Test-sized units: the cell's own traffic with fewer seeds per unit
# (and a hunt batch cut in proportion), so a CPU run takes seconds.
SMALL_SEEDS = {"sweep": 1024, "chaos": 256, "hunt": 4096}


@pytest.fixture(scope="session")
def small_cell():
    import run
    import spec

    jax = run.import_system()

    def make(name: str):
        _, cell, cfg, traffic = spec.load(name)
        w = SMALL_SEEDS[cell["traffic"]]
        kw = traffic["sweep_kwargs"]
        if "batch_worlds" in kw:
            kw["batch_worlds"] = kw["batch_worlds"] * w \
                // traffic["seeds_per_unit"]
            traffic["expect"]["admitted_at_least"] = kw["batch_worlds"]
        traffic["seeds_per_unit"] = w
        devices = jax.devices()[:int(cell["chips"])]
        return jax, cell, cfg, traffic, devices

    return make
