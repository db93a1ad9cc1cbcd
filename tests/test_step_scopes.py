"""The compiled step names its update and its guard (``trainer.update``,
``trainer.guard``) in the optimized HLO's metadata and nowhere else: the
lowered module, which is what jax's cache key hashes, is the same with and
without the scopes. ``compile.program_texts`` hands out the text of what a
site ran without touching what it runs."""
import contextlib
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile as C
from mxnet_tpu.gluon import loss as gloss, nn
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer, sharded_trainer

CASES = {
    "guard": (dict(nan_guard=True), "float32", {}),
    "no_guard": (dict(nan_guard=False), "float32", {}),
    "multi_precision": (dict(nan_guard=True), "bfloat16",
                        {"multi_precision": True}),
}


def _trainer(options, dtype, opt_params, optimizer="adam"):
    mx.random.seed(3)
    net = nn.HybridSequential()
    # tanh: no select of the network's own, so every select is the guard's
    net.add(nn.Dense(16, activation="tanh", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    if dtype != "float32":
        net.cast(dtype)
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(4, 8).astype(np.float32)).astype(dtype)
    y = mx.nd.array(rng.rand(4, 4).astype(np.float32)).astype(dtype)
    trainer = ShardedTrainer(
        net, gloss.L2Loss(), optimizer,
        dict({"learning_rate": 1e-2}, **opt_params),
        mesh=DeviceMesh({"dp": 1}), **options)
    return trainer, x, y


def _instructions(text, opcode):
    """``[(line, op_name)]`` of the HLO ``text``'s ``opcode`` lines."""
    out = []
    for line in text.splitlines():
        if re.search(rf"\s{re.escape(opcode)}\(", line):
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((line.strip(), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_scopes_leave_the_lowered_module_as_it_was(case, monkeypatch):
    """The cache-key guarantee: jax hashes the module after
    ``strip-debuginfo``, and the module printed without locations is byte
    for byte the same with ``jax.named_scope`` a null context (the small
    network holds no scope of its own)."""
    import jax

    options, dtype, opt_params = CASES[case]
    trainer, x, y = _trainer(options, dtype, opt_params)
    named = trainer.aot_lower(x, y)
    assert named.as_text() == named.as_text(debug_info=False)
    with_locations = named.as_text(debug_info=True)
    assert sharded_trainer.UPDATE_SCOPE in with_locations
    assert (sharded_trainer.GUARD_SCOPE in with_locations) \
        == options["nan_guard"]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    trainer, x, y = _trainer(options, dtype, opt_params)
    bare = trainer.aot_lower(x, y)
    assert sharded_trainer.UPDATE_SCOPE not in bare.as_text(debug_info=True)
    assert bare.as_text() == named.as_text()


@pytest.mark.parametrize("case", ["guard", "multi_precision"])
def test_compiled_text_names_update_and_guard(case):
    options, dtype, opt_params = CASES[case]
    trainer, x, y = _trainer(options, dtype, opt_params)
    text = trainer.aot_lower(x, y).compile().as_text()
    update, guard = sharded_trainer.UPDATE_SCOPE, sharded_trainer.GUARD_SCOPE
    # every select of the state (4 parameters x Adam's two moments, and the
    # parameter or its master) is the update's; no product is
    selects = _instructions(text, "select")
    slots = 3 * 4
    assert len(selects) >= slots
    assert all(update in name for _, name in selects), selects
    products = _instructions(text, "dot")
    assert products and not [p for p in products if "trainer." in p[1]]
    assert all("jvp(" in name for _, name in products), products
    finite = _instructions(text, "is-finite")
    assert finite and all(guard in name for _, name in finite), finite
    # the two names partition nothing else: the guard's is under no update
    assert not [n for _, n in finite if update in n]


def test_program_texts_hands_out_the_step_and_touches_nothing():
    C.clear_manifest()
    trainer, x, y = _trainer(*CASES["guard"])
    twin, _, _ = _trainer(*CASES["guard"])
    assert C.program_texts("trainer") == []     # nothing ran yet
    first = float(trainer.step(x, y).asscalar())
    assert float(twin.step(x, y).asscalar()) == first
    fn = trainer._step_fn
    seen, counters = dict(fn._seen), C.stats()["trainer"]
    texts = C.program_texts("trainer")
    mine = [t for t in texts if t["token"] == fn._token_key]
    assert len(mine) == 1 and len(texts) == 2   # the twin's step is the other
    assert mine[0]["module"] == "jit_step_fn"
    assert mine[0]["text"].startswith("HloModule jit_step_fn")
    assert sharded_trainer.UPDATE_SCOPE in mine[0]["text"]
    # no bookkeeping: the Compiled is dropped, no counter moves
    assert fn._seen == seen and C.stats()["trainer"] == counters
    assert C.program_texts("trainer") is texts           # memoized
    assert C.program_texts("no_such_site") == []
    # the next step is what it is without the call
    assert float(trainer.step(x, y).asscalar()) \
        == float(twin.step(x, y).asscalar())
    # another signature of the site: the list is made anew, and holds it
    x2 = mx.nd.array(np.ones((8, 8), np.float32))
    y2 = mx.nd.array(np.ones((8, 4), np.float32))
    trainer.step(x2, y2)
    again = C.program_texts("trainer")
    assert again is not texts and len(again) == 3


def test_program_texts_outlives_the_trainer():
    """A benchmark drops its trainer before it reads its trace: the miss
    kept the ``Lowered`` it made for the cost analysis (it pins no
    function and no buffer), so the text can still be had, once."""
    import gc
    import weakref

    C.clear_manifest()
    trainer, x, y = _trainer(*CASES["guard"])
    trainer.step(x, y)
    gone = weakref.ref(trainer._step_fn)
    del trainer
    gc.collect()
    assert gone() is None
    texts = C.program_texts("trainer")
    assert [t["module"] for t in texts] == ["jit_step_fn"]
    assert C.program_texts("trainer") is texts
    # what was pending is printed; nothing but the text is held now
    assert not C._LOWERED


def test_program_texts_compiles_nothing_anew():
    """``compile()`` on the kept lowering, and on one made again through
    the live function, hands back the executable jit made and ran: jax
    reports no backend compile while the text is printed."""
    import jax

    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    C.clear_manifest()
    trainer, x, y = _trainer(*CASES["guard"])
    trainer.step(x, y)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        assert len(C.program_texts("trainer")) == 1
        C._PROGRAMS.clear()                 # ask again: the live function
        assert not C._LOWERED and len(C.program_texts("trainer")) == 1
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert not compiles


def test_program_texts_lowers_through_the_live_function(monkeypatch):
    """Where no miss kept a lowering (telemetry off, a site without cost
    analysis) the recorded specs go through the function's own ``lower``,
    the way ``_warmup`` replays them; what that raises is raised."""
    C.clear_manifest()
    trainer, x, y = _trainer(*CASES["no_guard"])
    trainer.step(x, y)
    assert len(C._LOWERED) == 1
    C.clear_memory()
    assert not C._LOWERED
    lowered = []
    real = trainer._step_fn.lower
    monkeypatch.setattr(trainer._step_fn, "lower",
                        lambda *specs: lowered.append(specs) or real(*specs))
    assert [t["module"] for t in C.program_texts("trainer")] \
        == ["jit_step_fn"]
    assert len(lowered) == 1

    def refuse(*specs):
        raise RuntimeError("no lowering today")

    C._PROGRAMS.clear()
    monkeypatch.setattr(trainer._step_fn, "lower", refuse)
    with pytest.raises(RuntimeError, match="no lowering today"):
        C.program_texts("trainer")


def test_pending_lowerings_are_capped():
    C.clear_manifest()
    keep = [_trainer(*CASES["no_guard"]) for _ in range(6)]
    for trainer, x, y in keep:
        trainer.step(x, y)
    assert len(C._LOWERED) == C._LOWERED_CAP == 4
    # the two oldest lost their lowering and go through the live function
    assert len(C.program_texts("trainer")) == 6
    assert not C._LOWERED


def test_a_full_manifest_still_records_the_trainers_step(monkeypatch):
    """The per-op sites record first and ``_MANIFEST_CAP`` stops them; a
    step built after that still gets in, or ``program_texts`` would find
    nothing to replay."""
    C.clear_manifest()
    monkeypatch.setattr(C, "_MANIFEST_CAP", len(C.manifest()))
    (mx.nd.ones((3, 5)) * 2).wait_to_read()     # a per-op site: dropped
    assert C.manifest() == []
    trainer, x, y = _trainer(*CASES["no_guard"])
    trainer.step(x, y)
    assert [e["site"] for e in C.manifest()] == ["trainer"]
    assert len(C.program_texts("trainer")) == 1


def test_the_benchmark_reads_the_steps_own_names():
    """``chipbench/harness/step_phases.py`` repeats the two names (the
    benchmark imports nothing of the program's at module level): were one
    renamed here alone, the update's time would move into ``other`` with
    ``step_phase_unmatched_share`` still at 0."""
    from chipbench.harness import step_phases

    assert step_phases.UPDATE == sharded_trainer.UPDATE_SCOPE
    assert step_phases.GUARD == sharded_trainer.GUARD_SCOPE
    assert step_phases.SITE == "trainer"
