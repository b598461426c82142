"""Compile each benchmark cell's timed step for a described TPU v5e, at the
cell's own sizes, and hold its memory under one chip's 16 GB. Nothing
runs. The topology is described inside a fixture, never at import time,
and the persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_compile_v5e.py -s

prints each cell's bytes (arguments, outputs, temporaries).
"""
import os
import sys

import jax
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, program  # noqa: E402
from bench.configs import reference  # noqa: E402

CHIP_BYTES = 16e9


def _serve_cells():
    bench = harness.load_json(os.pardir, "BENCHMARK.json")
    return [w for w in bench["workloads"]
            if harness.load_json("traffic", w["traffic"] + ".json")
            ["driver"] == "serve"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                             # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def native_kernels(monkeypatch):
    """Compile the Pallas kernels as the chip runs them, not interpreted."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    monkeypatch.setattr(ops, "_interpret", lambda: False)


@pytest.mark.parametrize("workload", [w["name"] for w in _serve_cells()])
def test_serve_step_compiles_for_v5e(one_chip, native_kernels, workload):
    w = next(c for c in _serve_cells() if c["name"] == workload)
    config = harness.load_json("configs", w["config"] + ".json")
    traffic = harness.load_json("traffic", w["traffic"] + ".json")
    codec = harness.codec_of(config)
    spec = program.codec_spec(codec, use_kernel=True)
    cfg = program.serve_config(traffic, spec)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    state = placed(jax.eval_shape(lambda: program.serve_init_state(cfg)))
    dec = placed(jax.eval_shape(
        lambda k: reference.make_decoder(codec, config["weights"], k),
        jax.ShapeDtypeStruct((2,), "uint32")))
    compiled = program.serve_step(codec, cfg).lower(state, dec).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\n{workload}: arguments {mem.argument_size_in_bytes}, outputs "
          f"{mem.output_size_in_bytes}, aliased {mem.alias_size_in_bytes}, "
          f"temporaries {mem.temp_size_in_bytes}, total {total} bytes")
    assert total < CHIP_BYTES
    if codec["kind"] == "chunked_ae":
        assert "tpu_custom_call" in compiled.as_text()
