"""What the windowed correlation ``ops/pallas.windowed_corr_pyramid`` has
to do in one train step, from the cell's shapes: the yardstick's side of
``wcp_roofline``.

On each level it runs on, the operation dots frame one's feature at every
position with the (2r+1)^2 bilinear samples of that level's pooled map of
frame two round the position's centre, and its backward pass hands each
cost's cotangent back to the feature and to the samples' four taps. The
work is reckoned from the configuration (grid, channels, radius,
iterations, the features' width) and from the number of levels the
program says it computes this way (``wcp_levels_windowed``), never from a
call's operands: how the kernels split, lay out, pad or order that work
is theirs to change, and the yardstick stays where it is.

Least time is the larger of two floors. Bytes: every logical array once a
call, at its own size and width: frame one's features, the windowed
levels' pooled maps, the centres (float32) and the costs (float32)
forward; the same read again backward, with the costs' cotangent in the
costs' place, and the features' and maps' cotangents written at the
features' width. Operations: one multiply-add per tap and channel
forward, two backward (to the feature, to the map). More than that a
kernel may move or do; less it cannot, so the share cannot pass 100%.
"""

_F32 = 4


def shapes(config, batch):
    """``(b, h, w, c, radius, iterations, feature_bytes, levels)`` of a
    cell's configuration file: the 1/8 grid of its crop, the model's
    published widths."""
    p = config["model"]["model"]["parameters"]
    a = config["model"]["model"].get("arguments", {})
    height, width = config["train"]["crop"]
    return (int(batch), height // 8, width // 8,
            int(p.get("corr-channels", 256)), int(p.get("corr-radius", 4)),
            int(a.get("iterations", 12)),
            2 if p.get("mixed-precision") else 4,
            int(p.get("corr-levels", 4)))


def _positions(b, h, w, windowed):
    """Positions of the grid, and samples of the windowed levels' maps
    (level l at 1/2^l of the grid)."""
    return b * h * w, sum(b * (h >> l) * (w >> l) for l in range(windowed))


def forward_bytes(b, h, w, c, radius, feature_bytes, windowed):
    """Reads the features, the windowed levels' maps and the centres,
    writes the costs."""
    k2 = (2 * radius + 1) ** 2
    grid, maps = _positions(b, h, w, windowed)
    return (grid * c * feature_bytes + maps * c * feature_bytes
            + grid * 2 * _F32 + grid * windowed * k2 * _F32)


def backward_bytes(b, h, w, c, radius, feature_bytes, windowed):
    """Reads what the forward read and the costs' cotangent, writes the
    features' and the maps' cotangents."""
    grid, maps = _positions(b, h, w, windowed)
    return (forward_bytes(b, h, w, c, radius, feature_bytes, windowed)
            + (grid + maps) * c * feature_bytes)


def forward_macs(b, h, w, c, radius, windowed):
    return b * h * w * windowed * (2 * radius + 1) ** 2 * c


def least_seconds(config, batch, windowed, peaks):
    """The least time one train step's windowed correlation can take on a
    chip with ``peaks``: ``iterations`` forward and backward calls."""
    b, h, w, c, radius, iterations, fb, levels = shapes(config, batch)
    windowed = min(int(windowed), levels)
    moved = iterations * (
        forward_bytes(b, h, w, c, radius, fb, windowed)
        + backward_bytes(b, h, w, c, radius, fb, windowed))
    flops = iterations * 3 * 2 * forward_macs(b, h, w, c, radius, windowed)
    return max(moved / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"])
