"""Object kind ``train_state_rank``: the training state one expert-parallel
rank keeps of one MoE layer, a new one every step, and the plain safetensors
writer that says what a save of it has to store (the benchmark's copy of the
reference; ``tests/save_reference.py`` is the repository's).

Per parameter the rank holds (``n_routed_experts`` of the layer's published
experts, contiguous blocks by rank, and all of the layer that is not routed):
the bf16 weight and the float32 main copy and two float32 moments that
Megatron-LM's distributed optimizer keeps beside a bf16 parameter
(``optimizer.fp32_param.``, ``.exp_avg.``, ``.exp_avg_sq.`` + the name); the
router's float32 bias, which no gradient trains, stands alone. A tensor's
bytes are a pure function of (seed, step, name): finite normal floats.

The writer: ONE file; the tensors by item size, the widest first, then by
name, without a gap; the header ``json.dumps`` with the separators
``(",", ":")``, ``__metadata__`` first, padded with spaces so that the data
starts on a multiple of 8. Nothing here imports the program under test or
jax; the origin child serves nothing of this kind (``segments`` is a
placeholder: a saved state has no origin).
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import json
import zlib

import numpy as np

ITEM_BYTES = {"F32": 4, "BF16": 2}
JAX_DTYPE = {"F32": "float32", "BF16": "bfloat16"}
OPTIMIZER = ("optimizer.fp32_param.", "optimizer.exp_avg.",
             "optimizer.exp_avg_sq.")
LAYER = "model.layers.1."
THREADS = 8


def parameters(c: dict) -> list[tuple[str, tuple[int, ...]]]:
    """(name, shape) of the bf16 parameters the rank holds of the layer."""
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    kv_lora, expert = c["kv_lora_rank"], c["moe_intermediate_size"]
    parallel = c["deployment"]["expert_parallel"]
    rows = [
        ("input_layernorm.weight", (hidden,)),
        ("post_attention_layernorm.weight", (hidden,)),
        ("self_attn.q_proj.weight", (heads * (nope + rope), hidden)),
        ("self_attn.kv_a_proj_with_mqa.weight", (kv_lora + rope, hidden)),
        ("self_attn.kv_a_layernorm.weight", (kv_lora,)),
        ("self_attn.kv_b_proj.weight", (heads * (nope + v), kv_lora)),
        ("self_attn.o_proj.weight", (hidden, heads * v)),
        ("mlp.gate.weight", (parallel["n_routed_experts_published"], hidden)),
    ]
    first = parallel["rank"] * c["n_routed_experts"]
    mlps = [(f"mlp.experts.{e}.", expert)
            for e in range(first, first + c["n_routed_experts"])]
    mlps.append(("mlp.shared_experts.", c["n_shared_experts"] * expert))
    for prefix, width in mlps:
        rows += [(prefix + "gate_proj.weight", (width, hidden)),
                 (prefix + "up_proj.weight", (width, hidden)),
                 (prefix + "down_proj.weight", (hidden, width))]
    return [(LAYER + name, shape) for name, shape in rows]


def word_checksums(raw: np.ndarray, item: int) -> tuple[int, int]:
    """(sum mod 2**32, xor) of a tensor's items as unsigned integers of the
    item's width: what the driver's device program takes of a tensor."""
    items = np.ascontiguousarray(raw).view({2: "<u2", 4: "<u4"}[item])
    return (int(np.sum(items, dtype=np.uint64) & 0xFFFFFFFF),
            int(np.bitwise_xor.reduce(items)) if items.size else 0)


class Objects:
    """The rank's states of a configuration and seed: ``index`` is the
    step."""

    typed = True
    distinct = True

    def __init__(self, config: dict, seed: int):
        self.seed = seed
        parallel = config["deployment"]["expert_parallel"]
        self.table = [(LAYER + "mlp.gate.e_score_correction_bias", "F32",
                       (parallel["n_routed_experts_published"],))]
        for name, shape in parameters(config):
            self.table.append((name, "BF16", shape))
            self.table += [(prefix + name, "F32", shape)
                           for prefix in OPTIMIZER]
        # The file's order and header: the same for every step but for the
        # step the metadata names.
        self.order = sorted(self.table,
                            key=lambda t: (-ITEM_BYTES[t[1]], t[0]))
        self.nbytes = {name: int(np.prod(shape)) * ITEM_BYTES[dtype]
                       for name, dtype, shape in self.table}
        self.length = len(self.head(0)) + sum(self.nbytes.values())

    def size(self, index: int = 0) -> int:
        return self.length

    def segments(self, index: int = 0):
        """What the origin child asks for: nothing of this cell is served
        by an origin."""
        yield np.zeros(8, np.uint8)

    def cache_id(self, step: int) -> str:
        return f"s{self.seed}-step-{step}"

    def metadata(self, step: int) -> dict:
        return {"step": str(step), "rank": "0", "format": "pt"}

    def head(self, step: int) -> bytes:
        """The file's first bytes at ``step``: length prefix and header."""
        header: dict = {"__metadata__": self.metadata(step)}
        at = 0
        for name, dtype, shape in self.order:
            header[name] = {"dtype": dtype, "shape": list(shape),
                            "data_offsets": [at, at + self.nbytes[name]]}
            at += self.nbytes[name]
        text = json.dumps(header, separators=(",", ":")).encode()
        text += b" " * (-(8 + len(text)) % 8)
        return len(text).to_bytes(8, "little") + text

    def tensor_bytes(self, step: int, name: str, dtype: str) -> np.ndarray:
        """uint8 array of one tensor's bytes at one step."""
        size = self.nbytes[name]
        raw = np.random.PCG64(
            [self.seed, step, zlib.crc32(name.encode())]).random_raw(
            (size + 7) // 8)
        words = raw.view(np.uint32)
        if dtype == "BF16":
            words &= np.uint32(0x8FFF8FFF)
            words |= np.uint32(0x30003000)
        else:
            words &= np.uint32(0x8FFFFFFF)
            words |= np.uint32(0x30000000)
        return words.view(np.uint8)[:size]

    def state(self, step: int) -> dict:
        """name -> (dtype, shape, bytes) of the state at ``step``, made
        ``THREADS`` tensors at a time (the generator releases the GIL)."""
        with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
            made = list(pool.map(
                lambda t: self.tensor_bytes(step, t[0], t[1]), self.table))
        return {name: (dtype, shape, raw)
                for (name, dtype, shape), raw in zip(self.table, made)}

    def facts(self, step: int, state: dict) -> dict:
        """What a save of ``state`` has to store and a resume to bring
        back: the writer's file by length and sha256 (hashed in the file's
        order, never joined), and every tensor's (sum, xor)."""
        def digest() -> str:
            h = hashlib.sha256(self.head(step))
            for name, _, _ in self.order:
                h.update(state[name][2])
            return "sha256:" + h.hexdigest()

        with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
            hashed = pool.submit(digest)
            sums = list(pool.map(
                lambda t: word_checksums(state[t[0]][2], ITEM_BYTES[t[1]]),
                self.table))
            return {"length": self.length, "digest": hashed.result(),
                    "checksums": {t[0]: s for t, s in zip(self.table, sums)}}

    def matches(self, name: str, meta: tuple, got: np.ndarray,
                state: dict) -> bool:
        """A tensor fetched back whole, (dtype, shape) as the device says
        and its bytes, against the state's."""
        dtype, shape, raw = state[name]
        return (meta == (JAX_DTYPE[dtype], tuple(shape))
                and got.tobytes() == raw.tobytes())
