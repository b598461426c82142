"""Least-work counts: the operations and bytes that any correct
implementation of a serve round must spend, computed from the shapes in a
configuration and a traffic mix. Every share of a peak or roofline in this
benchmark divides the least time these give by a measured time, so no
correct implementation can read above 100%.

What is counted, and why it is a lower bound:

* The decoder's hidden layers run once per client on its latents (they
  are nonlinear, so nothing can be shared across clients).
* The last decoder layer is linear and shared, and the cohort weights sum
  to 1, so the weighted client reduction commutes with it: the reduction
  costs ``2·K·rows·d_in`` and the last layer is applied once per row, not
  once per client. Bias, activation, dequantization and denormalization
  are left out (elementwise work, below either bound).
* Bytes: each input is read once and each output written once — the
  cohort's payloads, every decoder parameter (float32, as the
  configuration stores them), and the stage's output. A round adds the
  arrival queue (every client's time and sequence number read once, to
  find the first K), the K re-dispatched entries written, the K versions
  read, and the global model read and written once.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

F32 = 4


def decoder_dims(codec: Dict) -> Tuple[int, List[int]]:
    """(rows per client, decoder widths from latent to output)."""
    if codec["kind"] == "chunked_ae":
        return (math.ceil(codec_size(codec) / codec["chunk_size"]),
                [codec["latent_chunk"], *reversed(codec["hidden"]),
                 codec["chunk_size"]])
    if codec["kind"] == "fc_ae":
        return 1, [codec["latent_dim"], *reversed(codec["encoder_hidden"]),
                   codec["input_dim"]]
    raise ValueError(f"unknown codec kind {codec['kind']!r}")


def codec_size(codec: Dict) -> int:
    """Length of the flat update (the loader copies it in from the
    configuration's ``model.update_size``)."""
    return codec["size"]


def payload_bytes(codec: Dict) -> int:
    """Wire bytes of one client's payload."""
    rows, dims = decoder_dims(codec)
    n_latent = rows * dims[0]
    q = codec.get("quantize")
    if q is None:
        return n_latent * F32
    nb = math.ceil(n_latent / q["block"])
    return nb * q["block"] * q["bits"] // 8 + nb * F32


def decoder_param_bytes(codec: Dict) -> int:
    _, dims = decoder_dims(codec)
    return sum((a * b + b) * F32 for a, b in zip(dims[:-1], dims[1:]))


def stage_flops(codec: Dict, k: int) -> float:
    """Decode→aggregate of a cohort of ``k`` into one mean update."""
    rows, dims = decoder_dims(codec)
    hidden = sum(2.0 * rows * a * b for a, b in zip(dims[:-2], dims[1:-1]))
    d_in, d_out = dims[-2], dims[-1]
    return k * hidden + 2.0 * k * rows * d_in + 2.0 * rows * d_in * d_out


def stage_bytes(codec: Dict, size: int, k: int) -> float:
    return (k * payload_bytes(codec) + decoder_param_bytes(codec)
            + size * F32)


def round_flops(codec: Dict, k: int) -> float:
    return stage_flops(codec, k)


def round_bytes(codec: Dict, size: int, n: int, k: int) -> float:
    """One serve round: pop, staleness weights, decode→aggregate, global
    update, re-dispatch."""
    queue = n * 2 * F32 + k * F32 + k * 3 * F32
    return (k * payload_bytes(codec) + decoder_param_bytes(codec)
            + queue + 2 * size * F32)
