"""Compile rehearsals for a TPU v5e: the main path's device programs at
full width, compiled for a described (not attached) chip.

Covers the lane replay program of each family at ATAX scale-1.0 shapes
(32 lanes x 32,768 accesses x 16,384-page span) and the simplified
predictor's train step and ``predict_cls_conf`` at their default widths.
A compile that passes is not a chip run: nothing here executes.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import os

import numpy as np
import pytest

LANES, T_MAX, SPAN = 32, 32768, 16384          # ATAX at scale 1.0
STEPS_LEN = 1024                               # step-clock windows
N_CLASSES = 20000                              # DeltaVocab's class cap


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile written to the persistent cache cannot be
    # read back without the chip: keep the cache out of these compiles
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _lane_arg_shapes(family, steps_len, mixed):
    """Argument shapes of a lane program, in the order
    ``PallasReplayBackend._replay_batch`` passes its arrays: pages, the
    family's extra streams, the step-id stream, then the float64 and
    int32 parameter blocks (the latter one column wider in a batch of
    several eviction policies)."""
    import jax
    import jax.numpy as jnp

    from repro.uvm.backends.pallas_backend import _N_FPARAMS, _N_IPARAMS

    def i32(n):
        return jax.ShapeDtypeStruct((LANES, n), jnp.int32)

    extra = {"learned": [i32(T_MAX)],
             "oracle": [i32(SPAN + _oracle_lookahead()), i32(T_MAX)]}
    return ([i32(T_MAX)] + extra.get(family, [])
            + ([i32(T_MAX)] if steps_len else [])
            + [jax.ShapeDtypeStruct((LANES, _N_FPARAMS), jnp.float64),
               i32(_N_IPARAMS + mixed)])


def _oracle_lookahead():
    from repro.uvm.prefetchers import OraclePrefetcher

    return OraclePrefetcher(np.arange(1)).lookahead


def _on(sharding, tree):
    import jax

    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("family,policies,steps_len,mt", [
    ("demand", ("lru",), STEPS_LEN, False),
    ("tree", ("random",), 0, True),
    ("learned", ("hotcold",), 0, False),
    ("oracle", ("lru",), 0, False),
    ("tree", ("hotcold", "random"), 0, False),
], ids=["demand-lru-steps", "tree-random-mt", "learned-hotcold",
        "oracle-lru", "tree-hotcold+random"])
def test_lane_program_compiles_for_v5e(one_chip, family, policies,
                                       steps_len, mt):
    import jax

    from repro.uvm.backends.pallas_backend import _lane_replay_fn
    from repro.uvm.config import UVMConfig

    lookahead = _oracle_lookahead() if family == "oracle" else 0
    ft_len = SPAN + lookahead if family == "oracle" else 0
    buf_len = UVMConfig().mshr_entries + 1
    with jax.enable_x64(True):
        fn = _lane_replay_fn(family, policies, LANES, T_MAX, SPAN, buf_len,
                             ft_len, lookahead, steps_len, mt)
        shapes = _on(one_chip, _lane_arg_shapes(family, steps_len,
                                                len(policies) > 1))
        compiled = fn.lower(*shapes).compile()
    assert compiled.memory_analysis() is not None
    # the lanes are an XLA program, not a Mosaic kernel
    assert "tpu_custom_call" not in compiled.as_text()


def _simplified_config():
    from repro.core import model as model_lib
    from repro.core.service import PredictorService

    svc = PredictorService()
    return model_lib.family_config(svc.model_family, N_CLASSES, 0.0,
                                   svc.bypass_threshold,
                                   quantize=svc.quantize), svc


def test_predictor_train_step_compiles_for_v5e(one_chip):
    import jax
    import jax.numpy as jnp

    from repro.core import model as model_lib
    from repro.core.train import make_train_step

    cfg, svc = _simplified_config()
    opt, step_fn = make_train_step(cfg, steps=svc.steps)
    params = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    x = jax.ShapeDtypeStruct((svc.batch_size, cfg.seq_len,
                              len(cfg.features)), jnp.int32)
    y = jax.ShapeDtypeStruct((svc.batch_size,), jnp.int32)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = step_fn.lower(*_on(one_chip, (params, opt_state, x, y,
                                             step))).compile()
    assert compiled.memory_analysis() is not None


def test_predict_cls_conf_compiles_for_v5e(one_chip):
    import inspect

    import jax
    import jax.numpy as jnp

    from repro.core import model as model_lib
    from repro.core.train import _jitted_cls_conf, predict_cls_conf

    cfg, _ = _simplified_config()
    batch = inspect.signature(predict_cls_conf).parameters[
        "batch_size"].default
    params = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((batch, cfg.seq_len, len(cfg.features)),
                             jnp.int32)
    compiled = _jitted_cls_conf(cfg).lower(
        *_on(one_chip, (params, x))).compile()
    assert compiled.memory_analysis() is not None
