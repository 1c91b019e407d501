"""Small configurations, mixes and cells for the CPU tests: the same keys as
the files under ``bench/configs``, ``bench/traffic`` and ``bench/cells``."""

import spec

# The small configurations are of the dense block: the architecture module
# that a configuration file naming no "arch", as phi4-mini's, is run with.
DENSE = spec.arch(spec.benchmark(), "phi4-mini-3.8b")

GQA = {
    "name": "small-gqa", "arch_type": "dense", "num_layers": 2, "d_model": 64,
    "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
    "vocab_size": 500, "vocab_pad": 256, "mlp_gated": True,
    "tie_embeddings": True, "rope_theta": 10000.0, "norm_eps": 1e-6,
    "dtype": "bfloat16",
}
MQA = dict(GQA, name="small-mqa", num_kv_heads=1, mlp_gated=False,
           tie_embeddings=False)

BACKLOG = {
    "arrival": {"kind": "backlog"},
    "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.6, "min": 8, "max": 40},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "block": 16,
    "warmup": {"steps": 2},
}
POISSON = dict(BACKLOG, arrival={"kind": "poisson", "rate": 20.0},
               warmup={"seconds": 0.5})

# At this width the program's widest gap reads 0 to 0.0041 and the fp8
# control's 0.0197 to 0.129 (CPU, seeds 3 to 10, windows of 1 s and 2 s), so
# the small cell's limit sits between them at 0.01, as each chip cell's
# limit sits between its two readings on the chip.
CELL = {
    "engine": {"batch": 4, "max_len": 256},
    "check": {"sample_tokens": 80, "max_requests": 8,
              "limits": {"served_gap_max": 0.01}},
}
TRACE_SECONDS = 1.0
