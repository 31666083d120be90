"""The serving path's share of the chip's peak: the operations the model
needs for every prompt token prefilled and every output token decoded in
the window, from shapes, over the window and the peak."""


def read(run):
    m = run.measures
    if not m.get("out_tokens"):
        return None
    ref, cfg = run.reference, run.cfg
    flops = m["out_tokens"] * ref.forward_flops_per_token(
        cfg, m["context_tokens"] / m["out_tokens"])
    if m.get("prompt_tokens"):
        mean_prompt = m["prompt_tokens"] / max(1, m["requests_sent"])
        flops += m["prompt_tokens"] * ref.forward_flops_per_token(
            cfg, (mean_prompt + 1) / 2.0)
    return 100.0 * flops / m["window_s"] / (run.chips * run.peaks["flops_per_s"])
