"""Operations and bytes of each kernel, at the configuration's real widths.

Every count here uses the unpadded layer sizes of the configuration file,
so a roofline share reads the same work whatever pads or tiles it: a change
that stops padding a layer to 128 lanes makes the kernel faster, not the
yardstick smaller.  Bytes are what the algorithm must move through HBM at
least once per launch: the rows in, the results out, and the network's
state read and written back.
"""

from __future__ import annotations

F32 = 4


def layer_sizes(config: dict) -> tuple:
    """(inputs, hidden..., outputs) of an MRF MLP configuration file."""
    return (2 * int(config["n_frames"]), *map(int, config["hidden"]),
            int(config["n_outputs"]))


def macs(sizes) -> int:
    """Multiply-accumulates of one forward pass of one row."""
    return sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def n_params(sizes) -> int:
    """Weights and biases of the network."""
    return macs(sizes) + sum(sizes[1:])


def forward_ops(sizes) -> int:
    """Operations of one row's forward pass (a MAC is two)."""
    return 2 * macs(sizes)


def train_ops_per_row(sizes) -> int:
    """Forward and backward operations of one training row: the forward
    pass, the weight gradients of every layer, and the input gradients of
    every layer but the first (nothing needs the gradient of the input)."""
    m = macs(sizes)
    first = sizes[0] * sizes[1]
    return 2 * m + 2 * m + 2 * (m - first)


def train_kernel_bytes(sizes, rows: int, launches: int, optimizer: str) -> int:
    """HBM bytes of ``launches`` fused training launches over ``rows`` rows:
    each row's features and targets in, one loss per launch's tiles out
    (counted with the rows, one f32 per 128 rows at most, so left out), and
    the network's weights, plus Adam's two moments, read once and written
    once per launch."""
    state = n_params(sizes) * (3 if optimizer == "adam" else 1)
    return rows * (sizes[0] + sizes[-1]) * F32 + launches * 2 * state * F32


def roofline_share(ops: float, nbytes: float, seconds: float,
                   ops_per_s: float, bytes_per_s: float) -> tuple:
    """(share of the roofline in %, the bound that sets it): the least time
    the chip could take for this work over the time it took."""
    t_ops, t_bytes = ops / ops_per_s, nbytes / bytes_per_s
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
