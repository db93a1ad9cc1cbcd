"""Test harness configuration.

Forces an 8-device virtual CPU mesh so multi-device sharding/collective
tests run on any host (parity trick: the reference tests multi-device logic
with multiple cpu Contexts, SURVEY §4; the TPU translation is XLA's
--xla_force_host_platform_device_count / jax_num_cpu_devices).

x64 is NOT enabled globally — production runs with it off, and the suite
must see production dtype semantics. float64 numeric-gradient checks scope
it locally via jax.enable_x64() (see test_utils).
Set MXNET_TEST_DEVICE=tpu:0 to run the suite against the real chip instead.
"""
import os

import jax

if os.environ.get("MXNET_TEST_DEVICE", "cpu").startswith("cpu"):
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)


# ------------------------------------------------- watchdog (observe mode) --
# CI hang diagnostics: a generous observe-mode deadline BELOW pytest's
# faulthandler_timeout (570s, pytest.ini) so a wedged test writes a crash
# bundle (all-thread tracebacks + last-N heartbeats) before faulthandler's
# stack dump fires — observe mode never interrupts anything and spawns no
# waiter threads. setdefault: an explicit MXNET_TPU_WATCHDOG wins. Tests
# that exercise the watchdog configure their own deadlines and restore the
# ambient config via watchdog.configure_from_env().
os.environ.setdefault("MXNET_TPU_WATCHDOG",
                      "*:540,action:observe,interval:60")

import numpy as _onp
import pytest as _pytest


@_pytest.fixture(autouse=True)
def _mxnet_test_seed():
    """Deterministic reruns under MXNET_TEST_SEED (parity: the reference
    test framework's with_seed decorator + tools/flakiness_checker)."""
    seed = os.environ.get("MXNET_TEST_SEED")
    if seed is not None:
        import mxnet_tpu as mx

        _onp.random.seed(int(seed))
        mx.random.seed(int(seed))
    yield


# ---------------------------------------------------------------- tiers ----
# Two test tiers (VERDICT r4 item 10): `pytest -m "not slow"` is the
# <3-minute smoke gate for inner-loop/driver use; the full suite stays the
# real gate. Slow = compile-heavy model sweeps, 2-process suites, and
# long-training tests, marked here centrally so the split is one list.
_SLOW_FILES = {
    "test_model_zoo.py",     # full model sweep, one XLA compile per arch
    "test_gluon_rnn.py",     # scan compiles + LM training
    "test_sparse_dist.py",   # 2-process distributed suites
    "test_onnx.py",          # export/import numeric roundtrips
    "test_op_sweep.py",      # 800-test registry-wide sweep (~2 min)
    "test_c_api.py",         # builds libmxtpu + four C host programs
}
_SLOW_TESTS = {
    "test_graft_entry_dryrun",
    "test_feedforward_legacy_api",
    "test_transformer_encoder_cell_trains",
    "test_multi_head_attention_kernel_path_and_export",
    "test_multi_head_attention_matches_oracle",
    "test_conv_rnn_cells",
    "test_norm_layers",
    "test_activations",
    "test_conv_layers",
    "test_train_conv",
    "test_train_mlp",
    "test_train_with_ndarray_iter_module_style",
    "test_gluon_data_pipeline_training_flow",
    "test_crash_course_gluon_train_loop",
    "test_module_workflow_checkpoints",
    "test_flash_gradients",
    "test_launch_local_sets_worker_env",
    "test_ring_attention_backward_matches_dense",
    "test_pipeline_parallel_matches_sequential",
    "test_amp_training_converges",
    "test_predict_abi_end_to_end",
    "test_sharded_trainer_matches_eager_optimizer",
    "test_factorization_machine_example",
    "test_transformer_finetune_example",
    "test_train_imagenet_benchmark_mode",
    "test_dcgan_example",
    "test_matrix_factorization_example",
    "test_multi_threaded_inference_abi",
    "test_sharded_trainer_multi_precision_master_weights",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        base = item.name.split("[")[0]
        if item.fspath.basename in _SLOW_FILES or base in _SLOW_TESTS:
            item.add_marker(_pytest.mark.slow)


# ------------------------------------------------------- per-test timeout --
# One hung test (deadlocked prefetch thread, wedged collective) must not
# eat the whole suite budget: raise TimeoutError inside the test after
# `test_timeout` seconds (pytest.ini; 0 disables). SIGALRM only fires on
# the main thread, which is where pytest runs tests; background threads a
# test spawned keep running and are the test's job to join. Complements
# the faulthandler_timeout stack dump (also pytest.ini).

def pytest_addoption(parser):
    parser.addini("test_timeout",
                  "per-test SIGALRM timeout in seconds (0 = off)",
                  default="0")


@_pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    import signal
    import threading

    seconds = int(item.config.getini("test_timeout") or 0)
    if (seconds <= 0 or not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        return (yield)

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded the {seconds}s per-test timeout "
            "(test_timeout in pytest.ini)")

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(seconds)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)
