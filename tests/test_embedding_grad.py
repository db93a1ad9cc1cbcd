"""The gradient of ``Embedding`` (``ops/tensor.py``): the table's cotangent
whole and in the blocks of columns ``embedding_grad_columns`` can choose,
against a float32 scatter-add: alone, under the tied table of the phi4flash
model and in a ``ShardedTrainer`` step on four CPU devices. The rule reads
shapes, so a test that wants blocks at a toy size puts them in the rule's
place."""
import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon.model_zoo import text
from mxnet_tpu.ops import tensor
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FOLDER = os.path.join(REPO, "chipbench", "configs", "phi4_mini_flash_l6")
SMALL = {"hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "sliding_window": 40, "vocab_size": 96}
VOCAB, WIDTH = 50, 24


def _zipf(n, vocab, seed=0):
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -1.0
    return np.random.default_rng(seed).choice(vocab, size=n, p=p / p.sum())


IDS = {
    "zipf_duplicates": _zipf(256, VOCAB),
    "unique": np.random.default_rng(1).permutation(VOCAB)[:32],
    "rank_2": _zipf(96, VOCAB, seed=2).reshape(4, 24),
    "out_of_range_and_negative": np.array([0, 3, 3, VOCAB, VOCAB + 7, -1,
                                           -VOCAB, -VOCAB - 1, 49]),
}


@pytest.fixture
def force(monkeypatch):
    """``force(columns)`` puts a block of ``columns`` columns (``None``:
    the table whole) in the rule's place. The op is traced once a shape, so
    what was traced under another rule is dropped, before and after."""
    def put(columns):
        jax.clear_caches()
        monkeypatch.setattr(tensor, "embedding_grad_columns",
                            lambda rows, vocab, width: columns or width)

    yield put
    jax.clear_caches()


def _table_grad(ids, cot, dtype):
    weight = jnp.zeros((VOCAB, WIDTH), dtype)
    _, pull = jax.vjp(
        lambda w: tensor._embedding(jnp.asarray(ids), w), weight)
    return pull(cot)[0]


def _reference(ids, cot):
    """float32 ``zeros.at[ids].add(cot)``, and what ``jnp.take``'s own
    transpose does with ids outside the table."""
    flat = np.asarray(ids).reshape(-1)
    flat = np.where(flat < 0, flat + VOCAB, flat)
    keep = (flat >= 0) & (flat < VOCAB)
    out = np.zeros((VOCAB, WIDTH), np.float32)
    np.add.at(out, flat[keep],
              np.asarray(cot, np.float32).reshape(-1, WIDTH)[keep])
    return out


def _take_transpose(ids, cot, dtype):
    _, pull = jax.vjp(
        lambda w: jnp.take(w, jnp.asarray(ids, jnp.int32), axis=0),
        jnp.zeros((VOCAB, WIDTH), dtype))
    return pull(cot)[0]


@pytest.mark.parametrize("kind", list(IDS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("columns", [None, 8, 16],
                         ids=["whole", "blocks_8_8_8", "blocks_16_8"])
def test_table_gradient_matches_the_float32_scatter_add(force, columns,
                                                        dtype, kind):
    force(columns)
    ids = IDS[kind]
    cot = jnp.asarray(np.random.default_rng(3).standard_normal(
        ids.shape + (WIDTH,), dtype=np.float32), dtype)
    got = _table_grad(ids, cot, dtype)
    assert got.shape == (VOCAB, WIDTH) and got.dtype == jnp.dtype(dtype)
    want = _reference(ids, cot)
    err = np.abs(np.asarray(got, np.float32) - want)
    # XLA's CPU backend rounds after every one of a row's duplicates
    ulp = {"bfloat16": 2.0 ** -8, "float32": 2.0 ** -23}[dtype]
    terms = np.bincount(np.asarray(ids).reshape(-1) % VOCAB).max()
    assert (err <= ulp * terms * (np.abs(want).max() + 1.0)).all(), err.max()
    # every element sums the same rows in the same order as the transpose
    # of jnp.take does, whatever the blocks: the same bits
    assert (np.asarray(_take_transpose(ids, cot, dtype))
            == np.asarray(got)).all()


def test_float_ids_and_the_forward_are_what_take_gives(force):
    """``F.arange`` hands BERT's position table float32 ids; the forward
    is ``jnp.take`` whatever the backward's blocks."""
    force(8)
    weight = jnp.asarray(np.random.default_rng(6).standard_normal(
        (VOCAB, WIDTH), dtype=np.float32), jnp.bfloat16)
    ids = jnp.arange(0, 12, dtype=jnp.float32)
    out, pull = jax.vjp(lambda w: tensor._embedding(ids, w), weight)
    assert (np.asarray(out) == np.asarray(weight[:12])).all()
    grad = np.asarray(pull(jnp.ones_like(out))[0], np.float32)
    assert (grad[:12] == 1).all() and (grad[12:] == 0).all()


def test_a_table_of_wider_rank_keeps_its_shape(force):
    """``jnp.take`` over axis 0 of a (vocab, 4, 6) table: the blocks are
    over the flattened row."""
    force(8)
    weight = jnp.zeros((VOCAB, 4, 6), jnp.float32)
    ids = jnp.asarray(IDS["rank_2"])
    cot = jnp.asarray(np.random.default_rng(8).standard_normal(
        ids.shape + (4, 6), dtype=np.float32))
    _, pull = jax.vjp(lambda w: tensor._embedding(ids, w), weight)
    got = np.asarray(pull(cot)[0])
    assert got.shape == (VOCAB, 4, 6)
    np.testing.assert_allclose(got.reshape(VOCAB, WIDTH),
                               _reference(ids, cot), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,vocab,width,columns", [
    (4096, 25008, 2560, 512),       # the hybrid decoder
    (8192, 16032, 2048, 512),       # the language model
    (12288, 30522, 768, 768),       # BERT's words
    (384, 512, 768, 768), (12288, 2, 768, 768),
    (4096, 200064, 2560, 2560),     # published tables: XLA goes row by row
    (8192, 128256, 2048, 2048),
    (32768, 200064, 2560, 512),     # ... until the batch makes it walk
    (0, 25008, 2560, 2560),
], ids=["phi4_flash", "kanana2", "bert_words", "bert_positions", "bert_types",
        "phi4_flash_published", "kanana2_published",
        "phi4_flash_published_32k_rows", "no_rows"])
def test_the_rule_at_the_cells_shapes(rows, vocab, width, columns):
    assert tensor.embedding_grad_columns(rows, vocab, width) == columns


# ---------------------------------------------- the tied table of a model -

@pytest.fixture(scope="module")
def model():
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(FOLDER, "model.py"))


def _cfg(dtype):
    from chipbench.harness import bench as hbench

    cfg = hbench.load_json(os.path.join(FOLDER, "config.json"))
    # one window-attention layer between the table and the tied head
    return copy.deepcopy({**cfg, **SMALL, "dtype": dtype,
                          "layers_kept": [1]})


def _net(model, cfg, seed=5):
    published, kept = model.model_config(cfg)
    net = text.get_model("phi4flash", layers_kept=kept, **published)
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    model._set_params(net, cfg, model.make_params(cfg, seed))
    return net


def _batch(cfg, b=4, s=64):
    ids = _zipf(b * (s + 1), cfg["vocab_size"], seed=7).reshape(b, s + 1)
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


@pytest.mark.parametrize("columns", [None, 32], ids=["whole", "blocks_of_32"])
def test_tied_table_gradient_matches_the_reference(force, model, columns):
    """Embedding and head share ``embed_weight``: its gradient is the
    head's dW plus the table's cotangent, against the float32 reference
    of the benchmark's configuration."""
    force(columns)
    cfg = _cfg("float32")
    x, y = _batch(cfg)
    net = _net(model, cfg)
    params = model.export_params(net, cfg)
    want = jax.grad(
        lambda p: model.reference(cfg, p, (x, y))["loss"])(params)
    with autograd.record():
        loss = model.loss(cfg)(net(mx.nd.array(x, dtype="int32")),
                               mx.nd.array(y, dtype="int32")).mean()
    loss.backward()
    name = next(n for n, _, _ in model.layout(cfg) if "embed" in n)
    got = net.embed_weight.grad().asnumpy()
    scale = float(np.abs(want[name]).max())
    assert scale > 0
    assert float(np.abs(got - np.asarray(want[name])).max()) <= 3e-3 * scale


def _train(model, force, columns, dtype, steps=3, **mesh):
    force(columns)
    cfg = _cfg(dtype)
    x, y = _batch(cfg)
    net = _net(model, cfg)
    trainer = ShardedTrainer(
        net, model.loss(cfg), "adam",
        {"learning_rate": 1e-3, "multi_precision": True}, **mesh)
    losses = [float(trainer.step(x, y).asscalar()) for _ in range(steps)]
    assert trainer.skipped_steps == 0
    return losses, np.asarray(net.embed_weight.data().asnumpy(), np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("zero", [False, True], ids=["dp", "zero"])
def test_step_on_four_devices_matches_one_device(force, model, zero, dtype):
    """Three Adam steps of the tied model over dp=4 (and with the state
    sharded) with the table's cotangent in blocks of 32 columns give the
    losses and the table of one device with it whole: each block
    partitions as the whole scatter does (partial sums over each device's
    rows, reduced over ``dp``)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    one, table_one = _train(model, force, None, dtype,
                            mesh=DeviceMesh({"dp": 1}))
    four, table_four = _train(
        model, force, 32, dtype,
        mesh=DeviceMesh({"dp": 4}, devices=jax.devices()[:4]), zero=zero)
    rtol = 1e-5 if dtype == "float32" else 2e-2
    assert four == pytest.approx(one, rel=rtol)
    assert four[-1] < four[0]
    # four devices sum a bfloat16 gradient in another order than one, and
    # Adam moves a weight by the learning rate whatever the gradient's size
    assert np.abs(table_four - table_one).max() <= \
        (1e-4 * np.abs(table_one).max() if dtype == "float32" else 3 * 1e-3)
