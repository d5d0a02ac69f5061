"""Model operations of every prefill and decode the window ran (prompt
tokens with the last one's logits; each decoded token of a live slot at
its context length), over the summed wall time of the engine steps that
ran them, over the chip's int8 peak (bfloat16 for a float engine)."""
from bench import work
from bench.traffic import bits


def read(run):
    c, cfg = run.window.counters, run.cell.config
    if not c["step_s"]:
        return None
    ops = sum(work.qwen3_prefill_ops(cfg, p) for p in c["prompts"])
    for steps, slots, kv in c["decode"]:
        ops += steps * slots * work.qwen3_token_ops(
            cfg, kv // max(1, steps * slots))
    pk = work.peaks(run.device_kind)
    peak = (pk["bf16_flops_per_s"] if bits(run.cell.traffic["precision"])
            is None else pk["int8_ops_per_s"])
    return 100.0 * ops / c["step_s"] / peak
