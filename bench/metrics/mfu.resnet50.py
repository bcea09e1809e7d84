"""ResNet-50's share of the chip's peak: frames per second in the window
x the network's FLOPs per frame (``bench/resnet_flops.py``, 8.18 GFLOP
at 224x224, from the configuration's shapes) / the bf16 peak (the GEMMs
run at the TPU's default precision, bf16 passes).  Moves
``frames_per_s``."""

from bench import resnet_flops


def read(ctx):
    if ctx["window_s"] <= 0 or not ctx["frames"]:
        return None
    per_s = ctx["frames"] / ctx["window_s"]
    return (100.0 * per_s * resnet_flops.flops_per_frame(ctx["config"])
            / ctx["peaks"]["bf16_flops"])
