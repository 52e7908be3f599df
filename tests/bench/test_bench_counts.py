"""Shape-based counts, the table of peaks, and the shares built on them."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import counts, device, harness

ROOT = Path(__file__).resolve().parents[2]
CONF = json.loads((ROOT / "bench/configs/internlm2-1.8b.json").read_text())
FAMILY = harness.load_family(CONF["model_type"])
V5E = device.PEAKS["TPU v5 lite"]


def test_matmul_params_by_hand():
    # per layer: q and o 2048 x 2048 each, k and v 2048 x 1024 each,
    # three 2048 x 8192 MLP matrices; plus the 2048 x 92,544 head
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert layer == 62_914_560
    assert FAMILY.matmul_params(CONF) == 24 * layer + 2048 * 92_544


def test_forward_flops_by_hand():
    n = FAMILY.matmul_params(CONF)
    # 10 tokens reading 55 keys in all (a causal prefill of 10)
    assert counts.prefill_keys(10) == 55
    assert counts.forward_flops(FAMILY, CONF, 10, 55) == \
        2 * n * 10 + 4 * 24 * 16 * 128 * 55


def test_paged_attention_work_by_hand():
    # rows of 17 and 16 live positions in blocks of 16: 2 + 1 blocks
    f, b = FAMILY.paged_attention_work(CONF, [17, 16], block_size=16)
    assert f == 4 * 16 * 128 * (17 + 16)
    kv = 2 * (2 + 1) * 16 * 8 * 128 * 2          # K and V, bf16
    qo = 2 * 2 * 16 * 128 * 2                    # q and out of both rows
    assert b == kv + qo


def test_roofline_names_its_bound():
    t, bound = counts.roofline_seconds(197e12, 1.0, V5E)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = counts.roofline_seconds(1.0, 819e9, V5E)
    assert (t, bound) == (pytest.approx(1.0), "memory")


@pytest.mark.parametrize("slack", [1.0, 1.5, 40.0])
def test_roofline_share_never_exceeds_one_on_consistent_inputs(slack):
    """The kernel roofline reader on a synthetic trace whose kernel calls
    each take ``slack`` times the least time the peaks allow."""
    from bench.harness import Run, load_reader
    from bench.trace import WINDOW_SPAN, Event, Trace
    lengths = [[130, 17, 1024], [131, 18]]               # two traced ticks
    t_call = [counts.roofline_seconds(*FAMILY.paged_attention_work(
        CONF, ls, block_size=16), V5E)[0] for ls in lengths]
    ops, t = [], 0.0
    for ti, ls in enumerate(lengths):
        for _ in range(CONF["num_hidden_layers"]):
            d = t_call[ti] * slack * 1e9
            ops.append(Event("%paged_decode_attention.1 = x", t, t + d))
            t += d
    tr = Trace(ops={0: ops}, spans=[Event(WINDOW_SPAN, 0, t)])
    ticks = [{"traced": True, "lengths": ls} for ls in lengths]
    run = Run(cell=SimpleNamespace(family=FAMILY), conf=CONF,
              traffic={"kv_block_size": 16},
              peaks=V5E, record={"ticks": ticks}, trace=tr)
    share = load_reader("paged_attn_roofline")(run)
    assert share == pytest.approx(100.0 / slack)
    assert share <= 100.0 + 1e-9


def test_unknown_device_kind_raises():
    with pytest.raises(device.NoChip):
        device.peaks_for("TPU v9 imaginary")
    assert device.peaks_for("TPU v5 lite") is V5E
