"""The (m, k, n) GEMMs of the benchmark's CNN calls, from the
configurations' shapes alone: ResNet-50 v1.5 on 64 frames, and
CIFAR_full on 64 frames and on one."""

from bench import core, flops, resnet_flops

_MANIFEST = core.load_manifest()
_RESNET50 = core.load_config(_MANIFEST, "resnet50")
_CIFAR_FULL = core.load_config(_MANIFEST, "cifar_full")

#: ResNet-50's 54 GEMMs of one 64-frame call, in the order they run
RESNET50_CALL = [g[1:] for g in resnet_flops.resnet_gemms(_RESNET50, 64)]
#: the distinct shapes of each configuration
MODEL_GEMMS = {
    "resnet50": sorted(set(RESNET50_CALL)),
    "cifar_full": sorted({g[1:] for frames in (64, 1)
                          for g in flops.cnn_gemms(_CIFAR_FULL, frames)}),
}
