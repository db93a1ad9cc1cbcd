"""The two readers of the expert layer's grouped products
(``moe_products_roofline_share``, ``moe_gmm_kernel_share``, over
``harness/grouped_products.py``) on a made-up trace that holds both
implementations' names: XLA's ``ragged-dot-*`` kernels and the Pallas
kernels of the ``grouped_matmul`` family, beside Mosaic calls and fusions
that are no grouped product; silent where the window holds no grouped
product, the trace no device or the configuration no expert layer."""
import os

import pytest

import chipbench_toy as toy

H, F, HELD = 2304, 896, 16
STEPS = 2


def _reader(name):
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(
        toy.BENCH, "layer_metrics", f"{name}.py"))


def _model(config):
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(
        toy.BENCH, "configs", config, "model.py"))


class _Experts:
    """Two expert layers that computed 16,384 and 15,360 live pairs a call
    over four calls."""

    @staticmethod
    def expert_load():
        return {1: {"pairs": [16384 * 4 / HELD] * HELD, "peak": 1,
                    "calls": 4},
                3: {"pairs": [15360 * 4 / HELD] * HELD, "peak": 1,
                    "calls": 4}}


def _events(kernel_us, xla_us):
    """Per product family (rows x f, rows x h, a weight cotangent either
    way round) one event of ``kernel_us`` under a Pallas kernel's name and
    one of ``xla_us`` under XLA's; then a flash call, a fusion with a row
    buffer's shape and a Mosaic call of another width, none a product."""
    mosaic = "{} custom-call:tpu_custom_call bf16[{}]"
    shapes = (f"17408,{F}", f"17408,{H}", f"{HELD},{H},{F}",
              f"{HELD},{F},{H}")
    events, at = [], 100
    for i, shape in enumerate(shapes):
        events.append([mosaic.format(f"jvp_moe.experts_.{i}", shape), at,
                       kernel_us * 1000])
        at += kernel_us * 1000
        events.append([mosaic.format(f"ragged-dot-none.{i}", shape), at,
                       xla_us * 1000])
        at += xla_us * 1000
    events += [[mosaic.format("jvp__.19", "32,8192,128"), at, 5000],
               [f"fusion.3 fusion:kLoop bf16[17408,{F}]", at + 5000, 5000],
               [mosaic.format("jvp__.20", "17408,128"), at + 10000, 5000]]
    return events, at + 15000


def _run(kernel_us=300, xla_us=100, model=_Experts, config=None):
    events, end = _events(kernel_us, xla_us)
    trace = {"devices": {"0": events}, "async": {},
             "host": {"main": [["chipbench.traced_window", 0, end + 10]]}}
    return {"mode": "train", "model": model, "trace": trace,
            "cfg": config or {"hidden_size": H, "moe_intermediate_size": F},
            "traffic": {"trace_steps": STEPS}, "chips": 1,
            "device": {"kind": "TPU v5 lite"}}


def test_roofline_share_reads_every_grouped_product():
    """11 products a layer of 2 x live x h x f: (16,384 + 15,360) x 22 x
    2,304 x 896 operations a step at 197 TFLOP/s, over the eight products'
    1.6 ms in two steps, whoever ran them."""
    reader = _reader("moe_products_roofline_share")
    run = _run()
    assert reader.applies(run)
    least_s = (16384 + 15360) * 22 * H * F / 197e12
    assert reader.compute(run) == pytest.approx(
        100 * least_s / (4 * 400e-6 / STEPS))


@pytest.mark.parametrize("kernel_us, xla_us, share", [
    (300, 100, 75.0), (0, 100, 0.0), (100, 0, 100.0)],
    ids=["both", "xla_only", "kernel_only"])
def test_gmm_kernel_share_splits_by_name(kernel_us, xla_us, share):
    """The Pallas kernels' part of the products' time: three quarters,
    none on a parent that runs XLA's kernels alone, all of it where the
    kernel took every product."""
    reader = _reader("moe_gmm_kernel_share")
    run = _run(kernel_us, xla_us)
    assert reader.applies(run)
    assert reader.compute(run) == pytest.approx(share)


@pytest.mark.parametrize("name", ["moe_products_roofline_share",
                                  "moe_gmm_kernel_share"])
def test_readers_are_silent_without_products(name):
    """No grouped product in the window, no device in the trace (the
    CPU), a configuration with no expert layer (BERT's) or no expert width:
    ``None``, not an error."""
    reader = _reader(name)
    no_product = _run()
    no_product["trace"]["devices"]["0"] = [
        e for e in no_product["trace"]["devices"]["0"]
        if "moe" not in e[0] and "ragged" not in e[0]]
    no_device = dict(_run(), trace={"devices": {}, "async": {}, "host": {}})
    bert = _run(model=_model("bert_base"),
                config={"hidden_size": 768, "intermediate_size": 3072})
    no_width = _run(config={"hidden_size": H})
    for run in (no_product, no_device, bert, no_width):
        assert reader.compute(run) is None
