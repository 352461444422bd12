"""Operations a training step requires and bytes the EF kernels must move.

Counted from the configuration's shapes, never from the program, so that no
program change can move the yardstick; per token and of parameters by the
configuration's family (``families/<family>.py``). Training is three
forward passes' worth (forward, and twice that backward).
"""

from __future__ import annotations

import families


def forward_flops_per_token(m: dict, seq: int) -> float:
    """``m`` holds the configuration file's keys (published names)."""
    return families.load(m).forward_flops_per_token(m, seq)


def train_flops_per_step(m: dict, tokens: int, seq: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq) * tokens


# ---------------------------------------------------------------------------
# HBM bytes of the bucket kernels (kernels/ef_sign.py), per call
# ---------------------------------------------------------------------------


def stats_bytes(nb: int, bs: int) -> float:
    """Per-bucket L1/L2 of g + e: read g and e, write two (nb,) columns."""
    return 2 * 4 * nb * bs + 2 * 4 * nb


def compress_bytes(nb: int, bs: int) -> float:
    """Sign, pack and residual: read g, e and the scales; write the words
    (one bit an element) and the new residual."""
    return 2 * 4 * nb * bs + 4 * nb + nb * bs / 8 + 4 * nb * bs


def decompress_mean_bytes(nb: int, bs: int, world: int) -> float:
    """Mean of W payloads: read W words and scales, write the (nb, bs) mean."""
    return world * (nb * bs / 8 + 4 * nb) + 4 * nb * bs


def param_count(m: dict) -> int:
    """Parameters the program holds for the configuration."""
    return families.load(m).param_count(m)


def n_buckets(m: dict, bucket_size: int) -> int:
    return -(-param_count(m) // bucket_size)
