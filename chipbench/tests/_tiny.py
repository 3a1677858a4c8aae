"""Tiny versions of the benchmark's cells, for runs on the CPU."""

import copy

import jax

from chipbench import harness

try:
    jax.config.update("jax_num_cpu_devices", 8)
except RuntimeError:  # backend already up, with the suite's 8 devices
    pass

TINY_MODEL = {"num_layers": 2, "d_model": 64, "d_ff": 160, "vocab_size": 256,
              "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
              "sliding_window": 64}
# between what sound runs of the tiny step read (loss 5e-05, first
# gradient 1e-03, change 1e-02 on the CPU) and what the faults read
TINY_TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 5e-2}


def spec(cell: str) -> dict:
    s = copy.deepcopy(harness.load_cell(cell))
    if cell.startswith("train"):
        s["config"]["program"]["smoke"] = True
        s["config"]["model"].update(TINY_MODEL)
        s["traffic"].update(seq=128, seqs_per_chip=4,
                            limits=dict(TINY_TRAIN_LIMITS))
    elif cell.startswith("a2a"):
        s["traffic"].update(tokens_per_block=8, sample_from_first=4,
                            sampled_calls=2)
    elif cell.startswith("plan"):
        s["traffic"].update(sample_from_first=8, sampled_requests=4)
    return s


def run(cell: str, seed: int = 2**31 + 99, seconds: float = 0.3) -> dict:
    """One run of the tiny cell through the harness, with the look for a
    chip skipped."""
    return harness.run_cell(cell, seed, seconds, False, require_tpu=False,
                            spec=spec(cell))
