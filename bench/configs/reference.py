"""Plain reference of one serve round, for the configurations beside this
file. It imports nothing of the program: the pop is a lexicographic sort,
the payloads are drawn by the traffic rule below, the decoder is written
out in ``jax.numpy``, and the weighted mean is a sum over client blocks.

The serve round (the semantics ``bench/drivers/serve.py`` holds the
program to):

1. pop the K clients with the smallest ``(time, seq)``, in that order;
2. the clock becomes ``max(clock, last popped time)``;
3. staleness ``s = version - versions[popped]``, weights
   ``(1 + s) ** -staleness_power`` normalized to sum 1;
4. the round's key is ``fold_in(PRNGKey(program_seed), next_seq)``, split
   into a payload key and a latency key; the payload key is split once per
   payload leaf, in the payload's leaf order, and each leaf is drawn from
   its key — floats standard normal, int8 codes uniform in [-127, 127];
5. the global model gains ``server_lr`` times the weighted mean of the
   decoded payloads;
6. the popped clients are re-dispatched: arrival ``clock + base_latency *
   (1 + jitter * (2u - 1))`` with ``u`` uniform from the latency key,
   sequence numbers ``next_seq + position``, version ``version + 1``.

``dtype`` and ``precision`` select the arithmetic: float32 at
``"highest"`` is the reference; bfloat16 throughout (weights, payloads,
activations, sums, the global model and the arrival times) is the
control, the step below the configuration's float32. XLA may keep a
narrower type's intermediate values at float32 (it allows excess
precision), which on the TPU would leave the control's sums and global
model in float32; so every value the control computes is rounded to its
type where it is made.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def _act(name: str, x):
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "linear":
        return x
    raise ValueError(f"unknown activation {name!r}")


def _round(x, dtype):
    """``x`` rounded to ``dtype``'s precision (a no-op for float32)."""
    if dtype == jnp.float32:
        return x
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def rows_per_client(codec: Dict) -> int:
    if codec["kind"] == "chunked_ae":
        return math.ceil(codec["size"] / codec["chunk_size"])
    return 1


def latent_width(codec: Dict) -> int:
    return (codec["latent_chunk"] if codec["kind"] == "chunked_ae"
            else codec["latent_dim"])


def out_width(codec: Dict) -> int:
    return (codec["chunk_size"] if codec["kind"] == "chunked_ae"
            else codec["input_dim"])


def decoder_layer_dims(codec: Dict) -> List[Tuple[int, int]]:
    hidden = (codec["hidden"] if codec["kind"] == "chunked_ae"
              else codec["encoder_hidden"])
    dims = [latent_width(codec), *reversed(hidden), out_width(codec)]
    return list(zip(dims[:-1], dims[1:]))


def payload_leaves(codec: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """The payload's leaves in leaf order: (name, shape, dtype)."""
    n_latent = rows_per_client(codec) * latent_width(codec)
    q = codec.get("quantize")
    if q is None:
        return [("z", (n_latent,), "float32")]
    if q["bits"] != 8:
        raise ValueError("the reference draws int8 codes only")
    nb = math.ceil(n_latent / q["block"])
    return [("q", (nb, q["block"]), "int8"), ("scales", (nb,), "float32")]


def make_decoder(codec: Dict, weights: Dict, key: jax.Array) -> Dict:
    """The decoder the server holds, drawn from ``key``: a list of
    ``{"w", "b"}`` layers and the normalizer. Float32, one jitted call."""
    dims = decoder_layer_dims(codec)

    def draw(key):
        keys = jax.random.split(key, 2 * len(dims))
        layers = []
        for i, (a, b) in enumerate(dims):
            layers.append({
                "w": jax.random.normal(keys[2 * i], (a, b), jnp.float32)
                * (a ** -0.5),
                "b": jax.random.normal(keys[2 * i + 1], (b,), jnp.float32)
                * weights["bias_scale"]})
        return {"dec": layers,
                "norm": {"mean": jnp.float32(weights["norm_mean"]),
                         "std": jnp.float32(weights["norm_std"])}}

    return jax.jit(draw)(key)


def draw_payloads(codec: Dict, key: jax.Array, k: int) -> Dict[str, jax.Array]:
    leaves = payload_leaves(codec)
    keys = jax.random.split(key, len(leaves))
    out = {}
    for kk, (name, shape, dt) in zip(keys, leaves):
        if dt == "float32":
            out[name] = jax.random.normal(kk, (k, *shape)).astype(jnp.float32)
        else:
            out[name] = jax.random.randint(kk, (k, *shape), -127, 128,
                                           dtype=jnp.int32).astype(jnp.int8)
    return out


def decode_rows(codec: Dict, dec: Dict, payload: Dict[str, jax.Array],
                dtype, precision) -> jax.Array:
    """Payloads of ``c`` clients → their decoded updates ``(c, size)``."""
    c = next(iter(payload.values())).shape[0]
    rows, lat = rows_per_client(codec), latent_width(codec)
    if "q" in payload:
        z = _round(payload["q"].astype(dtype)
                   * payload["scales"].astype(dtype)[..., None],
                   dtype).reshape(c, -1)
    else:
        z = payload["z"].astype(dtype)
    x = z[:, :rows * lat].reshape(c * rows, lat)
    layers = dec["dec"]
    for i, layer in enumerate(layers):
        x = _round(jnp.dot(x, layer["w"].astype(dtype), precision=precision,
                           preferred_element_type=dtype), dtype)
        x = _round(x + layer["b"].astype(dtype), dtype)
        last = i == len(layers) - 1
        x = _act(codec["final_activation"] if last else codec["activation"],
                 x)
    x = _round(_round(x * dec["norm"]["std"].astype(dtype), dtype)
               + dec["norm"]["mean"].astype(dtype), dtype)
    return x.reshape(c, rows * out_width(codec))[:, :codec["size"]]


def serve_round(state: Dict, dec: Dict, *, codec: Dict, traffic: Dict,
                program_seed: int, server_lr: float, dtype=jnp.float32,
                precision="highest", block: int = 64) -> Dict:
    """One serve round on ``state`` (the program's state layout), computed
    in ``dtype``: returns ``(next state, the round's mean update)``. Jit it
    with everything but ``state`` and ``dec`` bound."""
    k = traffic["buffer_k"]
    block = math.gcd(k, block)
    times = state["times"].astype(dtype)
    seqs = state["seqs"]
    order = jnp.lexsort((seqs, times))[:k]
    clock = jnp.maximum(state["clock"].astype(dtype), times[order[-1]])
    stale = (state["version"] - state["versions"][order]).astype(dtype)
    w = _round((1.0 + stale) ** jnp.asarray(-traffic["staleness_power"],
                                            dtype), dtype)
    w = _round(w / _round(jnp.sum(w), dtype), dtype)

    key = jax.random.fold_in(jax.random.PRNGKey(program_seed),
                             state["next_seq"])
    k_pay, k_lat = jax.random.split(key)
    payload = draw_payloads(codec, k_pay, k)

    def body(acc, blk):
        pl, wb = blk
        rows = decode_rows(codec, dec, pl, dtype, precision)
        part = _round(jnp.einsum("c,cp->p", wb, rows, precision=precision,
                                 preferred_element_type=dtype), dtype)
        return _round(acc + part, dtype), None

    nblk = k // block
    blocks = (jax.tree_util.tree_map(
        lambda a: a.reshape(nblk, block, *a.shape[1:]), payload),
        w.reshape(nblk, block))
    mean, _ = jax.lax.scan(body, jnp.zeros((codec["size"],), dtype), blocks)
    glob = _round(state["global_flat"].astype(dtype)
                  + _round(jnp.asarray(server_lr, dtype) * mean, dtype),
                  dtype)

    u = jax.random.uniform(k_lat, (k,), dtype=jnp.float32)
    lat = _round((traffic["base_latency"]
                  * (1.0 + traffic["jitter"] * (2.0 * u - 1.0))).astype(dtype),
                 dtype)
    new = dict(state)
    new.update({
        "times": times.at[order].set(_round(clock + lat, dtype)).astype(
            jnp.float32),
        "seqs": seqs.at[order].set(
            state["next_seq"] + jnp.arange(k, dtype=jnp.int32)),
        "versions": state["versions"].at[order].set(state["version"] + 1),
        "global_flat": glob.astype(jnp.float32),
        "clock": clock.astype(jnp.float32),
        "version": state["version"] + 1,
        "next_seq": state["next_seq"] + jnp.int32(k),
    })
    return new, mean.astype(jnp.float32)
