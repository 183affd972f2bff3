"""ConvNeXt-T (Liu et al., "A ConvNet for the 2020s", arXiv:2201.03545):
widths (96, 192, 384, 768), depths (3, 3, 9, 3), 224x224x3 images, 1000
classes; 28,589,128 parameters."""
import dataclasses

from repro.core.types import ArchConfig

CONFIG = ArchConfig(
    name="convnext-tiny", family="convnext",
    convnext_dims=(96, 192, 384, 768), convnext_depths=(3, 3, 9, 3),
    cnn_input=(224, 224), n_classes=1000,
    param_dtype="float32", scan_layers=False, remat=False,
)


def smoke_config() -> ArchConfig:
    """Every kind of layer (stem, three downsamples, blocks, head) at tiny
    widths on 64x64 images."""
    return dataclasses.replace(CONFIG, convnext_dims=(8, 16, 24, 32),
                               convnext_depths=(1, 1, 2, 1),
                               cnn_input=(64, 64), n_classes=10)
