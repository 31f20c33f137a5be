"""What "a rank saves its training state into the fabric and a replacement
host resumes it" has to mean, in plain Python and numpy.

The state of one expert-parallel rank of a DeepSeek-V3-shaped MoE layer: per
parameter the bf16 weight and, as Megatron-LM's distributed optimizer keeps
beside bf16 parameters, the float32 main copy and two float32 moments
(``optimizer.fp32_param.``, ``.exp_avg.``, ``.exp_avg_sq.`` + the name); the
router's float32 bias, which no gradient trains, stands alone. Every value
is a pure function of (seed, step, tensor name): finite normal floats (the
exponent's top three bits 011), as the benchmark's generators make them.

The writer: ONE safetensors file. The tensors lie by item size, the widest
first, then by name, without a gap; the header is ``json.dumps`` with the
separators ``(",", ":")``, ``__metadata__`` first where there is any, then
the tensors in the file's order, padded with spaces so that the data starts
on a multiple of 8 bytes. (A second writer must make these choices to give
the same bytes; a reader needs none of them.)

An acknowledged save: the ack names ``replicas`` distinct hosts, the saver
among them; the scheduler's record of the task agrees; every holder's stored
content is the writer's bytes, by length and sha256. A resume: the saver's
copy gone, every tensor comes back bit-identical from the other holders,
and no byte from any origin.

It imports nothing of the program and no jax. The benchmark has its copy
with its generator (``chipbench/objects/train_state_rank.py``); the tests in
``test_save_reference.py`` hold the program to this one.
"""

from __future__ import annotations

import hashlib
import json
import zlib

import numpy as np

ITEM_BYTES = {"F32": 4, "BF16": 2, "U8": 1, "I8": 1}
OPTIMIZER = ("optimizer.fp32_param.", "optimizer.exp_avg.",
             "optimizer.exp_avg_sq.")
LAYER = "model.layers.1."


def parameters(w: dict) -> list[tuple[str, tuple[int, ...]]]:
    """(name, shape) of the bf16 parameters one rank holds of one layer."""
    hidden, heads = w["hidden_size"], w["num_attention_heads"]
    nope, rope, v = (w["qk_nope_head_dim"], w["qk_rope_head_dim"],
                     w["v_head_dim"])
    kv_lora, expert = w["kv_lora_rank"], w["moe_intermediate_size"]
    rows = [
        ("input_layernorm.weight", (hidden,)),
        ("post_attention_layernorm.weight", (hidden,)),
        ("self_attn.q_proj.weight", (heads * (nope + rope), hidden)),
        ("self_attn.kv_a_proj_with_mqa.weight", (kv_lora + rope, hidden)),
        ("self_attn.kv_a_layernorm.weight", (kv_lora,)),
        ("self_attn.kv_b_proj.weight", (heads * (nope + v), kv_lora)),
        ("self_attn.o_proj.weight", (hidden, heads * v)),
        ("mlp.gate.weight", (w["n_routed_experts_published"], hidden)),
    ]
    first = w["rank"] * w["n_routed_experts"]
    mlps = [(f"mlp.experts.{e}.", expert)
            for e in range(first, first + w["n_routed_experts"])]
    mlps.append(("mlp.shared_experts.", w["n_shared_experts"] * expert))
    for prefix, width in mlps:
        rows += [(prefix + "gate_proj.weight", (width, hidden)),
                 (prefix + "up_proj.weight", (width, hidden)),
                 (prefix + "down_proj.weight", (hidden, width))]
    return [(LAYER + name, shape) for name, shape in rows]


def table(w: dict) -> list[tuple[str, str, tuple[int, ...]]]:
    """(name, safetensors dtype, shape) of every tensor of the state."""
    rows = [(LAYER + "mlp.gate.e_score_correction_bias", "F32",
             (w["n_routed_experts_published"],))]
    for name, shape in parameters(w):
        rows.append((name, "BF16", shape))
        rows += [(prefix + name, "F32", shape) for prefix in OPTIMIZER]
    return rows


def tensor_bytes(seed: int, step: int, name: str, dtype: str,
                 shape) -> np.ndarray:
    """uint8 array of one tensor's bytes at one step."""
    size = int(np.prod(shape)) * ITEM_BYTES[dtype]
    raw = np.random.PCG64([seed, step, zlib.crc32(name.encode())]) \
        .random_raw((size + 7) // 8)
    words = raw.view(np.uint32)
    if dtype == "BF16":
        words &= np.uint32(0x8FFF8FFF)
        words |= np.uint32(0x30003000)
    elif dtype == "F32":
        words &= np.uint32(0x8FFFFFFF)
        words |= np.uint32(0x30000000)
    return words.view(np.uint8)[:size]


def state(seed: int, step: int, w: dict) -> dict:
    """name -> (dtype, shape, bytes) of the rank's state at ``step``."""
    return {name: (dtype, shape, tensor_bytes(seed, step, name, dtype, shape))
            for name, dtype, shape in table(w)}


def write(tensors: dict, metadata: dict | None = None) -> bytes:
    """The safetensors file of ``tensors`` (name -> (dtype, shape, bytes))
    as the module's docstring lays it out."""
    order = sorted(tensors, key=lambda n: (-ITEM_BYTES[tensors[n][0]], n))
    header: dict = {}
    if metadata:
        header["__metadata__"] = {str(k): str(v) for k, v in metadata.items()}
    at = 0
    for name in order:
        dtype, shape, raw = tensors[name]
        assert len(raw) == int(np.prod(shape)) * ITEM_BYTES[dtype]
        header[name] = {"dtype": dtype, "shape": list(shape),
                        "data_offsets": [at, at + len(raw)]}
        at += len(raw)
    text = json.dumps(header, separators=(",", ":")).encode()
    text += b" " * (-(8 + len(text)) % 8)
    return b"".join([len(text).to_bytes(8, "little"), text,
                     *(bytes(tensors[name][2]) for name in order)])


def word_checksums(raw) -> tuple[int, int]:
    """(sum32, xor32) of bytes as little-endian uint32 words, the last
    padded with zeros."""
    raw = bytes(raw) + b"\0" * (-len(raw) % 4)
    words = np.frombuffer(raw, "<u4")
    return (int(np.sum(words, dtype=np.uint64) & 0xFFFFFFFF),
            int(np.bitwise_xor.reduce(words)) if words.size else 0)


def not_acknowledged(holders: list, stat: dict, saver: str, replicas: int,
                     stored: dict, content: bytes) -> list[str]:
    """Why this is no acknowledged save; [] where it is one. ``holders``:
    the ack's host ids; ``stat``: the scheduler's record of the task;
    ``stored``: host id -> the bytes that host's store holds of it."""
    why = []
    if len(set(holders)) < replicas or saver not in holders:
        why.append(f"the ack names {holders}, not {replicas} hosts with "
                   f"{saver} among them")
    recorded = {p["host_id"] for p in stat.get("peers", ())
                if p["state"] == "succeeded"}
    if stat.get("state") != "succeeded" or not set(holders) <= recorded:
        why.append(f"the scheduler records {stat.get('state')} at "
                   f"{sorted(recorded)}")
    want = hashlib.sha256(content).hexdigest()
    if stat.get("digest") != "sha256:" + want:
        why.append(f"the scheduler's digest is {stat.get('digest')}")
    for host in holders:
        got = stored.get(host)
        if got is None or hashlib.sha256(got).hexdigest() != want:
            why.append(f"{host} does not hold the writer's bytes")
    return why
