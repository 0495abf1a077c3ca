"""MobileNet-v1 1.0-224 on the blocked direct-conv layers.

Howard et al. 2017, "MobileNets: Efficient Convolutional Neural Networks
for Mobile Vision Applications", arXiv:1704.04861, Table 1: a 3x3 stride-2
stem convolution to 32 channels, 13 depthwise-separable blocks (3x3
depthwise + 1x1 pointwise, each followed by a nonlinearity), a global
average pool over the final 7x7x1024 map and a 1024 -> 1000 classifier.
The widths and strides below are the paper's, at width multiplier 1.0 and
resolution 224.  (Table 1 prints the last depthwise layer as "s2" on a
7x7 input whose output is also 7x7; it is stride 1, as every released
implementation has it.)

What the blocked layers change: no batch normalization (the repo's layers
carry a bias and ReLU in the fused epilogue instead), and the average pool
rides the last pointwise conv's epilogue (``BlockedCNN``'s fused GAP).
Every width is the published one; channel pencils target 128 lanes.
"""
from __future__ import annotations

from repro.nn.conv import BlockedCNN, BlockedConv2D, DepthwiseSeparableBlock

__all__ = ["INPUT_SIZE", "N_CLASSES", "STEM", "BLOCKS", "mobilenet_v1"]

INPUT_SIZE = 224
N_CLASSES = 1000
STEM = (3, 32, 2)                 # (ci, co, stride) of the 3x3 stem conv
# (ci, co, depthwise stride) of the 13 depthwise-separable blocks
BLOCKS = ((32, 64, 1), (64, 128, 2), (128, 128, 1), (128, 256, 2),
          (256, 256, 1), (256, 512, 2),
          (512, 512, 1), (512, 512, 1), (512, 512, 1), (512, 512, 1),
          (512, 512, 1),
          (512, 1024, 2), (1024, 1024, 1))


def mobilenet_v1(precision: str = "f32", lane: int = 128) -> BlockedCNN:
    """The full network as a ``BlockedCNN`` (stem, 13 blocks, fused GAP,
    linear head), every layer at the published width."""
    ci, co, stride = STEM
    convs = [BlockedConv2D(ci=ci, co=co, hf=3, wf=3, stride=stride,
                           padding="SAME", activation="relu", lane=lane,
                           precision=precision)]
    convs += [DepthwiseSeparableBlock(ci=ci, co=co, hf=3, wf=3, stride=s,
                                      padding="SAME", activation="relu",
                                      lane=lane, precision=precision)
              for ci, co, s in BLOCKS]
    return BlockedCNN(convs=tuple(convs), n_classes=N_CLASSES)
