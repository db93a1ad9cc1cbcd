"""The attention backward as Pallas calls, seen from the benchmark: the
reader of its dispatch counter, and the forward's roofline reader among
the new calls' names (a trace names a call after its FIRST result)."""
import os

import pytest

import chipbench_toy as toy


def _load(*parts):
    from chipbench.harness import bench as hbench

    path = os.path.join(toy.BENCH, *parts)
    return hbench.load_json(path) if path.endswith(".json") \
        else hbench.load_module(path)


def _run(stats):
    return {"mode": "train", "dispatch_stats": stats}


@pytest.mark.parametrize("stats,want", [
    # a program without the family (the parent), or without attention
    ({}, None),
    ({"flash_attention": {"kernel": 5, "xla": 0}}, None),
    ({"flash_attention_bwd": {"kernel": 0, "xla": 0}}, None),
    # five layers traced twice (the donating step): all kernel
    ({"flash_attention_bwd": {"kernel": 10, "xla": 0}}, 100.0),
    # a tuned row sent one bucket to the scan
    ({"flash_attention_bwd": {"kernel": 9, "xla": 3}}, 75.0),
    ({"flash_attention_bwd": {"kernel": 0, "xla": 12}}, 0.0),
])
def test_flash_backward_kernel_share_counts_the_backwards_family(stats,
                                                                 want):
    reader = _load("layer_metrics", "flash_backward_kernel_share.py")
    assert reader.applies(_run(stats))
    assert not reader.applies({"mode": "serve", "dispatch_stats": stats})
    got = reader.compute(_run(stats))
    assert got == want if want is None else got == pytest.approx(want)
    assert (reader.LAYER, reader.MOVES, reader.UNIT) \
        == ("kernels", "train_samples_per_s", "%")


def test_flash_backward_kernel_share_reads_the_real_counter():
    """The counter the reader reads is the one ``kernels.dispatch`` keeps
    for the backward's own family, by that name."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels

    q = jnp.ones((1, 2, 128, 32), jnp.float32)
    kernels.reset_stats()
    jax.grad(lambda a: kernels.dispatch(
        "flash_attention", a, a, a, 0.2, causal=True,
        interpret=True).sum())(q)
    reader = _load("layer_metrics", "flash_backward_kernel_share.py")
    twin = _load("layer_metrics", "flash_kernel_share.py")
    run = _run(kernels.dispatch_stats())
    assert reader.compute(run) == 100.0 and twin.compute(run) == 100.0


def test_flash_roofline_share_counts_the_forward_among_the_new_calls():
    """The step's attention calls as the chip prints them since the
    backward is kernels: a forward whose result is a tuple (output, row
    log-sum-exp), named after the output; a dK/dV call named after dK and
    a dQ call, both as wide as the keys. Six steps of five 3 ms forwards
    against the 8.72 ms roofline: the five forwards, and nothing else."""
    from chipbench.harness import trace_reduce

    reader = _load("layer_metrics", "flash_roofline_share.py")
    cfg = _load("configs", "kanana2_30b_a3b_ep8", "config.json")
    model = _load("configs", "kanana2_30b_a3b_ep8", "model.py")
    target = 'custom_call_target="tpu_custom_call"'
    hlo = {
        "forward": "%jvp_mla.attention_.7 = (bf16[64,4096,128]{2,1,0:T(8,"
                   "128)(2,1)}, f32[64,4,1,1024]{3,2,1,0:T(1,128)}) "
                   f"custom-call(bf16[64,4096,192]{{2,1,0}} %q), {target}",
        "dkv": "%transpose_jvp_mla.attention_.3 = (bf16[64,4096,192]"
               "{2,1,0:T(8,128)(2,1)}, bf16[64,4096,128]{2,1,0:T(8,128)"
               f"(2,1)}}) custom-call(bf16[64,4096,192]{{2,1,0}} %q), "
               f"{target}",
        "dq": "%transpose_jvp_mla.attention_.4 = bf16[64,4096,192]{2,1,0:"
              "T(8,128)(2,1)} custom-call(bf16[64,4096,192]{2,1,0} %q), "
              f"{target}",
        "experts": "%ragged-dot.1 = bf16[49152,768]{1,0} custom-call("
                   f"bf16[49152,2048]{{1,0}} %x), {target}",
    }
    names = {k: trace_reduce.short_name(v) for k, v in hlo.items()}
    assert names["forward"] == ("jvp_mla.attention_.7 custom-call:"
                                "tpu_custom_call bf16[64,4096,128]")
    assert names["dkv"].endswith("tpu_custom_call bf16[64,4096,192]")
    assert names["dq"].endswith("tpu_custom_call bf16[64,4096,192]")
    events, t = [], 0
    for _ in range(6 * 5):
        for key, ns in (("forward", 3_000_000), ("experts", 1_000_000),
                        ("dkv", 9_000_000), ("dq", 7_000_000)):
            events.append([names[key], t, ns])
            t += ns
    traffic = {"global_batch": 2, "seq_len": 4096, "trace_steps": 6}
    run = {"mode": "train", "model": model, "cfg": cfg, "traffic": traffic,
           "trace": {"devices": {"0": events}, "async": {}, "host": {}},
           "device": {"kind": "TPU v5 lite"}, "chips": 1}
    least = model.attention_kernel_cost(cfg, traffic)["flops"] / 197e12
    assert reader.compute(run) == pytest.approx(100 * least / 15e-3)
    # and every Mosaic call of the step is Pallas time
    pallas = _load("layer_metrics", "pallas_time_share.py")
    assert pallas.compute(run) == pytest.approx(100.0)
