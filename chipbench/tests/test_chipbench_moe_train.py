"""The MoE training cell, tiny, on the CPU: the sound step is correct on
one chip and, expert-parallel, on the 2x2 of its EP group (the cell's
traffic on a (pod 2, data 2) mesh), and the cell's counts and readers give
what the chip runs read.  The faults are in
``test_chipbench_moe_faults.py``."""

import copy
import types

import jax
import pytest

from chipbench import harness
from chipbench.tests import _tiny  # noqa: F401  (the suite's 8 CPU devices)

CELL = "train.deepseek-v2-lite.1chip"
EP, ONE = (2, 2, 1), (1, 1, 1)  # the EP group's mesh, and the cell's
SEED = 2**31 + 11
# the smoke architecture's widths, in the configuration file's keys
TINY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128,
               "moe_intermediate_size": 32, "n_routed_experts": 16,
               "num_attention_heads": 4, "kv_lora_rank": 32,
               "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
               "v_head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256}
# between what sound runs of the tiny 2x2 step read on the CPU (seeds
# 2**31 + 11 and 12: loss 2.8e-04, first gradient 5.5e-03, change 4.0e-02)
# and what the control and the faults read (the float8 control 2.8e-03,
# 0.079, 0.075; no exchange 3.1e-03, 0.080, 0.11; half batch 0.52 on the
# gradient)
TINY_LIMITS = {"loss_gap": 1e-3, "grad_gap": 2e-2, "change_gap": 5e-2}


def spec(mesh: tuple) -> dict:
    s = copy.deepcopy(harness.load_cell(CELL))
    s["traffic"]["mesh"] = list(mesh)
    s["cell"]["chips"] = mesh[0] * mesh[1] * mesh[2]
    s["config"]["program"]["smoke"] = True
    s["config"].update(TINY_WIDTHS)
    s["config"]["model"].update(num_layers=2, vocab_size=256,
                                experts_per_chip=4)
    s["traffic"].update(seq=32, seqs_per_chip=2, log_every=2,
                        limits=dict(TINY_LIMITS))
    return s


def run(mesh: tuple, seconds: float = 0.5) -> dict:
    return harness.run_cell(CELL, SEED, seconds, False, require_tpu=False,
                            spec=spec(mesh))


@pytest.mark.parametrize("mesh", [EP, ONE], ids=["ep2x2", "1chip"])
def test_sound_step_is_correct(mesh):
    out = run(mesh)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_window_info_feeds_the_readers():
    s = spec(EP)
    entry = harness.load_entry(s["traffic"]["entry"])
    cell = entry.build(s["config"], s["traffic"], SEED, jax.devices()[:4])
    cell.setup()
    res = cell.window(0.5, traced=False)
    info = res["info"]
    assert info["moe_routed"] > 0 and 0 <= info["moe_dropped"] < \
        info["moe_routed"]
    ctx = types.SimpleNamespace(info=info, trace=None, spans=None, chips=4,
                                peaks=None)
    share = harness.load_reader("moe_drop_share")(ctx)
    assert share == pytest.approx(100 * info["moe_dropped"]
                                  / info["moe_routed"])


def test_flops_per_token_at_published_widths():
    """The MoE-aware count at the cells' sizes: 6 x the active matmul
    weights (6 routed experts on the 2x2, the held 1.5 on one chip) plus
    attention at 4096 tokens."""
    from chipbench.entries import train_moe_step as e

    cfg = harness.load_cell(CELL)["config"]
    ep = e.train_flops_per_token(cfg, 4, 4096)
    one = e.train_flops_per_token(cfg, 1, 4096)
    assert ep == pytest.approx(3.11e9, rel=0.01)
    assert one == pytest.approx(2.18e9, rel=0.01)
    assert ep - one == pytest.approx(6 * 4 * 4.5 * 3 * 2048 * 1408)
