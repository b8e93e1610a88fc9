"""Operations per sample, from shapes alone (never from XLA's cost_analysis).

ResNet v1 (He et al. 2015, arXiv:1512.03385, Table 1) as the gluon model zoo
builds it: a 7x7/2 stem, 3x3/2 max-pool, four stages of blocks, global
average pool, one dense layer. Only convolutions and the dense layer are
counted (multiply-adds; BatchNorm, ReLU, pooling and the residual adds are
bandwidth, not arithmetic). The zoo's v1 bottleneck strides in its first 1x1
convolution, so the 3x3 of a stage's first block runs at the reduced size.
"""

# depth -> (block kind, blocks per stage); widths are the paper's
RESNET_V1 = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
}
STAGE_WIDTH = (64, 128, 256, 512)       # the 3x3 convolutions' channels


def _conv(cin, cout, kernel, out_hw):
    return cin * cout * kernel * kernel * out_hw * out_hw


def resnet_v1_forward_macs(depth, image_size=224, num_classes=1000):
    """Multiply-adds of one forward pass of one image."""
    kind, blocks = RESNET_V1[depth]
    expansion = 4 if kind == "bottleneck" else 1
    hw = image_size // 2                        # 7x7 stride 2
    macs = _conv(3, 64, 7, hw)
    hw //= 2                                    # max-pool stride 2
    cin = 64
    for stage, (n_blocks, width) in enumerate(zip(blocks, STAGE_WIDTH)):
        cout = width * expansion
        for block in range(n_blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            out_hw = hw // stride
            if kind == "bottleneck":
                macs += _conv(cin, width, 1, out_hw)      # strided 1x1
                macs += _conv(width, width, 3, out_hw)
                macs += _conv(width, cout, 1, out_hw)
            else:
                macs += _conv(cin, width, 3, out_hw)
                macs += _conv(width, width, 3, out_hw)
            if block == 0 and (stride != 1 or cin != cout):
                macs += _conv(cin, cout, 1, out_hw)       # projection
            cin, hw = cout, out_hw
    return macs + cin * num_classes


def forward_flops(config):
    """Floating-point operations (2 per multiply-add) of one sample's
    forward pass, for a configuration file's `flops` block."""
    spec = config["flops"]
    if spec["family"] != "resnet_v1":
        raise KeyError(f"flops.py knows no family {spec['family']!r}")
    return 2 * resnet_v1_forward_macs(spec["depth"], config["image_size"],
                                      config["num_classes"])


def train_flops(config):
    """Forward + backward: the backward pass does two products for each
    one of the forward pass. Recomputation is not counted."""
    return 3 * forward_flops(config)
