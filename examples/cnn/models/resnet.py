import hetu_tpu as ht
from .common import conv2d, bn, fc, ce_loss


def _basic_block(x, in_ch, out_ch, stride, name, df):
    shortcut = x
    x = bn(conv2d(x, in_ch, out_ch, 3, stride, 1, name + "_c1",
                  data_format=df), out_ch, name + "_bn1", relu=True,
           data_format=df)
    x = bn(conv2d(x, out_ch, out_ch, 3, 1, 1, name + "_c2",
                  data_format=df), out_ch, name + "_bn2", data_format=df)
    if in_ch != out_ch or stride > 1:
        shortcut = bn(conv2d(shortcut, in_ch, out_ch, 1, stride, 0,
                             name + "_cs", data_format=df), out_ch,
                      name + "_bns", data_format=df)
    return ht.relu_op(x + shortcut)


_LAYERS = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3)}


def resnet(x, y_, num_layers=18, num_class=10, data_format="NCHW"):
    """ResNet-18/34, CIFAR stem (reference examples/cnn/models/ResNet.py).

    ``data_format``: the feed stays NCHW (reference/torch convention);
    "NHWC" transposes ONCE at the stem and keeps activations channels-last
    through the network — the layout the TPU wants (C on the 128-lane
    axis).  On XLA-CPU channels-last composed slower (its NCHW pipeline
    already relayouts internally where profitable), so NCHW stays the
    default; ``tools/audit_graphs.py`` builds NHWC, the program for the TPU.
    """
    df = data_format
    if df == "NHWC":
        x = ht.transpose_op(x, perm=(0, 2, 3, 1))
    reps = _LAYERS[num_layers]
    x = bn(conv2d(x, 3, 64, 3, 1, 1, "stem", data_format=df), 64,
           "stem_bn", relu=True, data_format=df)
    in_ch = 64
    for stage, (rep, ch) in enumerate(zip(reps, (64, 128, 256, 512))):
        for r in range(rep):
            stride = 2 if (stage > 0 and r == 0) else 1
            x = _basic_block(x, in_ch, ch, stride, f"s{stage}b{r}", df)
            in_ch = ch
    x = ht.avg_pool2d_op(x, 4, 4, 0, 4, data_format=df)
    x = ht.array_reshape_op(x, output_shape=(-1, 512))
    logits = fc(x, (512, num_class), "head")
    return ce_loss(logits, y_)


def resnet18(x, y_, num_class=10, data_format="NCHW"):
    return resnet(x, y_, 18, num_class, data_format)


def resnet34(x, y_, num_class=10, data_format="NCHW"):
    return resnet(x, y_, 34, num_class, data_format)
