"""The model-FLOP formula against a count by hand, for the configuration
as run (one layer) and at the published depth."""
import json

import pytest

from chipbench import flops
from chipbench.harness import HERE


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
