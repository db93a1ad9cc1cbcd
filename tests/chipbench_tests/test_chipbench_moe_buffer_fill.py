"""The ``moe_buffer_fill`` reader (PR 38) on made-up observations: the live
pairs a call over the rows a grouped product had, and silent where there
is no expert layer, no device in the trace or no grouped product in it."""
import os

import pytest

import chipbench_toy as toy


def _reader(name):
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(
        toy.BENCH, "layer_metrics", f"{name}.py"))


def _model(config):
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(
        toy.BENCH, "configs", config, "model.py"))


def _buffer_run(rows, model=None):
    """Two expert layers that computed 8,198 and 8,190 pairs a call over
    two calls; in the traced window 18 grouped products with a (rows,
    width) result (forward, recompute and dX of two layers), 6 with a
    weight's (n, h, f) cotangent, a product of ``rows // 2`` rows outside
    the window and a fusion of ``rows`` rows that is no product."""
    class Model:
        @staticmethod
        def expert_load():
            return {2: {"pairs": [8198 * 2 / 8] * 8, "peak": 1, "calls": 2},
                    3: {"pairs": [8190 * 2 / 8] * 8, "peak": 1, "calls": 2}}

    product = "ragged-dot-none.{} custom-call:tpu_custom_call bf16[{}]"
    events = [[product.format(i, f"{rows},1792"), 100 + i, 1]
              for i in range(18)]
    events += [[product.format(18 + i, "8,2048,1792"), 200 + i, 1]
               for i in range(6)]
    events += [[product.format(30, f"{rows // 2},2048"), 2000, 1],
               [f"fusion.3 fusion:kCustom bf16[{rows},2048]", 300, 1]]
    trace = {"devices": {"0": events}, "async": {},
             "host": {"main": [["chipbench.traced_window", 50, 950]]}}
    return {"mode": "train", "model": model or Model, "trace": trace}


@pytest.mark.parametrize("rows", [8704, 32768], ids=["rung", "every_pair"])
def test_moe_buffer_fill_reads_live_pairs_over_buffer_rows(rows, capsys):
    """The mean live pairs a call over the mean rows a grouped product had:
    8,194 over 8,704 rows, 94.1 %; over a row for every pair, 25.0 %."""
    reader = _reader("moe_buffer_fill")
    run = _buffer_run(rows)
    assert reader.applies(run)
    assert reader.compute(run) == pytest.approx(100 * 8194 / rows)
    assert f'# moe_buffer_rows: {{"{rows}": 18}}' in capsys.readouterr().out


def test_moe_buffer_fill_is_silent_without_experts_or_products():
    """No expert layer (the other cells), no device in the trace (the
    CPU), no grouped product in it: ``None``, not an error."""
    reader = _reader("moe_buffer_fill")
    bert = _buffer_run(8704, model=_model("bert_base"))
    no_device = dict(_buffer_run(8704), trace={
        "devices": {}, "async": {}, "host": {}})
    no_product = _buffer_run(8704)
    no_product["trace"]["devices"]["0"] = [
        e for e in no_product["trace"]["devices"]["0"]
        if not e[0].startswith("ragged")]
    for run in (bert, no_device, no_product):
        assert reader.compute(run) is None
