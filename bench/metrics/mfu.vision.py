"""Model operations per image (every conv and the fc) times the images
per second of the traced run's window, over the chip's peak for the
cell's arithmetic: int8 for <W:I> up to 8 bits, bfloat16 for float."""
from bench import work
from bench.traffic import bits


def read(run):
    c, cfg = run.window.counters, run.cell.config
    if not c["images"]:
        return None
    ops = work.model_ops(work.resnet50_gemms(cfg["image_size"],
                                             cfg["num_labels"]))
    pk = work.peaks(run.device_kind)
    peak = (pk["bf16_flops_per_s"] if bits(run.cell.traffic["precision"])
            is None else pk["int8_ops_per_s"])
    return 100.0 * ops * c["images"] / c["window_s"] / peak
