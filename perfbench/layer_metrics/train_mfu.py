"""Model FLOP/s utilisation of the training cells, in percent of the chip's
bf16 peak: tokens/s/chip x FLOPs a token (forward + backward, recompute not
counted) over the peak."""
from perfbench.harness.arith import rate, train_flops_per_token


def read(run):
    if run["job"] != "train" or not run["peaks"]:
        return None
    s, tr = run["shape"], run["train"]
    tok_s_chip = rate(tr["tokens"], run["window"]["seconds"], run["chips"])
    flops = train_flops_per_token(tr["n_params"], s["layers"], s["hidden"],
                                  tr["seq"])
    return 100.0 * tok_s_chip * flops / run["peaks"]["bf16_flops"]
