"""The arithmetic of the self-check's throw-away configuration: GPT-2's
under the configuration's own keys, and a kernel family more,
``commit``, whose calls run under the scope ``update`` (the fused msgd
commit), so that a family's own reader has something to read."""

from chipbench.arithmetic import gpt2


def _as_gpt2(c):
    return {"n_embd": c["width"], "n_head": c["heads"], "n_layer": c["depth"],
            "n_inner": c["ffn"], "n_positions": c["context"],
            "vocab_size": c["rows"]}


def param_count(c):
    return gpt2.param_count(_as_gpt2(c))


def train_flops_per_token(c):
    return gpt2.train_flops_per_token(_as_gpt2(c))


def kernels(c, batch):
    n = param_count(c)
    return {
        "attn": gpt2.kernels(_as_gpt2(c), batch)["attn"],
        # v = mom * v - lr * g; w += v: three reads, two writes, 3 FLOPs
        "commit": {"scope": "update", "flops": 3.0 * n, "bytes": 5.0 * 4 * n,
                   "least_calls": 1},
    }


def hand_worked():
    tiny = {"width": 64, "heads": 4, "depth": 1, "ffn": 256, "context": 128,
            "rows": 320}
    # tables 320 x 64 + 128 x 64 = 28,672; one layer 256 + 16,384 + 16,640
    # + 16,448 = 49,728; final LayerNorm 128; head 20,480: 99,008
    return [("parameters of the throw-away configuration at its small size",
             param_count(tiny), 99_008)]
