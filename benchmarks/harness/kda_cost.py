"""What the chunked gated delta-rule kernel `kda_scan` NEEDS, for the
readers of `kernel.kda_scan_roofline.closed` and the KDA families' cost
functions: ONE count (`ray_tpu/ops/kda.scan_cost`'s arithmetic, kept here
so that the yardstick does not import the program; the families' tests
hold the two equal, and `CHUNK` equal to every served KDA
configuration's `kda_chunk`)."""
from __future__ import annotations

CHUNK = 32      # positions a chunk, in every served KDA configuration


def scan_cost(H: int, d: int, positions: float, rows: float, halved: bool,
              chunk: int = CHUNK) -> tuple[float, float]:
    """(flops, bytes) ONE KDA layer's `kda_scan` calls NEED for
    `positions` true positions in `rows` prompts at H heads of [d, d]: q,
    k, g, v in and o out once (float32), beta, the state written a prompt;
    and a (head, chunk)'s products as the kernel forms them: A and B once
    under a gate BOUNDED below (the chunk's middle anchors every pair) and
    once a LEVEL of the halved anchors without a bound (`halved`:
    log2(chunk) masked products over the chunk), the 2 (log2(chunk) - 1)
    products of the inverse by halves, W and U0, `[Qd; W] S`, `B U`,
    `Ke^T U`, a multiply-add two operations."""
    C = chunk
    levels = (C - 1).bit_length()
    a_chunk = ((levels if halved else 1) * 2 * C * C * (2 * d)
               + 2 * max(0, levels - 1) * 2 * C ** 3
               + 2 * C * C * (2 * d) + 2 * (2 * C) * d * d
               + 2 * C * C * d + 2 * C * d * d)
    nbytes = 4 * H * (5 * d + 1) * positions + 4 * H * d * d * rows
    return float(a_chunk) * H * positions / C, float(nbytes)
