"""A tiny configuration and tiny mixes for the CPU self-tests: the same
code paths as a cell on the chip, at sizes a test run can hold."""
import copy
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

CFG = {
    "name": "tiny-moe", "source": "test", "architecture": "moe_transformer",
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "vocab_size": 500, "tie_word_embeddings": False, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "qk_norm": True, "dtype": "bfloat16",
    "xshare": {"mode": "batch", "k0": 1, "m_l": 2},
}

CLOSED = {
    "loop": "closed", "clients": 8, "slots": 4, "cache_len": 48,
    "admission": "affinity", "decode_chunk": 4,
    "prompt_len": {"values": [16], "weights": [1.0]},
    "output_len": {"median": 14, "sigma": 0.5, "min": 8, "max": 24},
    "topics": ["gpqa", "aime2025", "mmlu-pro", "aa-lcr"], "block": 8,
    "max_group": {16: 4}, "max_queue": 8, "check_steps": 40,
}

OPEN = dict(copy.deepcopy(CLOSED), loop="open", rate_per_s=6.0,
            lead_in_s=0.5, prompt_len={"values": [8, 16],
                                       "weights": [0.5, 0.5]},
            max_group={8: 4, 16: 4})
OPEN.pop("clients")

# Sound runs of the tiny model read a widest gap of 0.0002 to 0.008 and
# the fp8 control 0.027 to 0.038 (CPU, seeds 1-4): the tests' limit.
GAP_LIMIT = 0.015

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e9}


class Meter:
    def between(self, t0, t1):
        return []


class Dev:
    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def run(mix, lim=None, *, seed=12345678901, seconds=2.0, fault=None,
        cfg=CFG, control=False):
    import runner
    lim = lim or {"logit_gap_max": 0.05, "tokens_checked_min": 50}
    cell = {"name": "tiny", "traffic": "tiny", "chips": 1}
    return runner.run_cell(cell, cfg, mix, lim, seed=seed, seconds=seconds,
                           traced=False, meter=Meter(),
                           t_process=time.perf_counter(), devices=[Dev()],
                           device_peaks=PEAKS, fault=fault, control=control)
