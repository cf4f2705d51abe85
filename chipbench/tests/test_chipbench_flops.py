"""The model-FLOP formula against a count by hand, for the configuration
as run (one layer) and at the published depth, for latent attention with
experts (published and one chip's share), and for a stack it cannot
count."""
import dataclasses
import json

import pytest

from chipbench import flops
from chipbench.harness import HERE, Run, load_reader
from repro.configs import ARCHS


def _cfg(layers=None):
    cfg = json.loads((HERE / "configs" / "smollm-135m-1l.json").read_text())
    if layers is not None:
        cfg = dict(cfg, n_layers=layers)
    return cfg


def _by_hand(layers):
    # per layer q,o 2*d*h*hd, k,v 2*d*kv*hd, MLP 3*d*f, then the head d*V;
    # dense = 6 * that * tokens; attention = 3 passes * L * 4*B*S^2*h*hd
    return dict(
        matmul=layers * (2 * 576 * 9 * 64 + 2 * 576 * 3 * 64
                         + 3 * 576 * 1536) + 576 * 49152,
        attention=3 * layers * 4 * 16 * 2048 ** 2 * 9 * 64)


@pytest.mark.parametrize("layers", [None, 30])
def test_train_step_flops_by_hand(layers):
    cfg = _cfg(layers)
    hand = _by_hand(cfg["n_layers"])
    assert flops.matmul_params(cfg) == hand["matmul"]
    got = flops.train_step_flops(cfg, 16, 2048)
    assert got["dense"] == 6 * hand["matmul"] * 16 * 2048
    assert got["attention"] == hand["attention"]
    assert got["total"] == got["dense"] + got["attention"]


def test_smollm_step_is_40_tflop():
    assert flops.train_step_flops(_cfg(30), 16, 2048)["total"] \
        == 40_355_512_713_216
    # one layer: 6.73 TFLOP, 93% of it the dense products
    assert flops.train_step_flops(_cfg(), 16, 2048)["total"] \
        == 6_725_918_785_536


def _arch(name):
    """An ``ARCHS`` entry as a configuration file holds it."""
    return json.loads(json.dumps(dataclasses.asdict(ARCHS[name])))


def test_deepseek_v2_lite_by_hand():
    """Latent attention, a dense first layer and 26 expert layers."""
    cfg = _arch("deepseek-v2-lite-16b")
    # per layer, MLA: q 2048*16*192, down 2048*(512+64), up 512*16*(128+128),
    # out 16*128*2048; the dense layer 3*2048*10944; an expert layer: the
    # router 2048*64, 6 routed and 2 shared experts of 3*2048*1408
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    expert = 2048 * 64 + (6 + 2) * 3 * 2048 * 1408
    assert (mla, expert) == (13_762_560, 69_337_088)
    hand = 27 * mla + 3 * 2048 * 10944 + 26 * expert + 2048 * 102400
    assert flops.matmul_params(cfg) == hand == 2_451_308_544   # "2.4B active"
    got = flops.train_step_flops(cfg, 16, 2048)
    assert got["dense"] == 6 * hand * 16 * 2048
    assert got["attention"] == 3 * 27 * 2 * 16 * 2048 ** 2 * 16 * (192 + 128)
    assert got["total"] == got["dense"] + got["attention"]


def test_held_experts_are_charged_their_share_of_the_routed_work():
    """One chip's share: the dense layer and 5 expert layers, 8 of the 64
    experts held (the router at 64, top-6), an eighth of the vocabulary:
    0.75 routed expert MLPs a token."""
    cfg = _arch("deepseek-v2-lite-16b")
    cfg.update(n_layers=6, vocab_size=12_800,
               moe=dict(cfg["moe"], n_routed=8, n_routed_total=64))
    mla = 13_762_560
    expert = 2048 * 64 + 6 * 8 * 3 * 2048 * 1408 // 64 \
        + 2 * 3 * 2048 * 1408
    hand = 6 * mla + 3 * 2048 * 10944 + 5 * expert + 2048 * 12_800
    assert flops.matmul_params(cfg) == hand == 295_632_896


def test_uncounted_stack_reads_no_mfu():
    """A stack of recurrent blocks (xLSTM) is not counted, and step_mfu
    then reads nothing from a trace it would otherwise read."""
    cfg = _arch("xlstm-1.3b")
    assert flops.matmul_params(cfg) is None
    assert flops.train_step_flops(cfg, 16, 2048) is None
    read = load_reader("step_mfu")
    run = Run(cell=None, peaks={"bf16_flops_per_s": 1e12}, steps=4,
              trace={"modules": {"jit_step": (4, 2.0)}})
    run.flops_per_step = 1e11
    assert read(run) == pytest.approx(20.0)
    run.flops_per_step = None
    assert read(run) is None
