"""Model FLOPs per token (the family's ``train_flops_per_token``) times
tokens/s per chip, over the chip's bf16 peak (bench/peaks.json), in
percent."""


def read(run):
    if not run.get("tokens_per_s_per_chip"):
        return None
    return (100.0 * run["flops_per_token"] * run["tokens_per_s_per_chip"]
            / run["peaks"]["bf16_flops_per_s"])
