"""Object kind ``safetensors_shard``: one checkpoint shard made from a seed.

The file holds ``model.embed_tokens`` and one whole MoE layer at the widths
the configuration file states, one tensor per expert matrix under
DeepSeek-V3 names, and behind them two tensors of random bits that are not
the model's. Each tensor's bytes are a pure function of (seed, tensor
index), so the origin child and the checking process make the same bytes
without sharing any. Nothing here imports the program under test or jax.
"""

from __future__ import annotations

import concurrent.futures
import json
import struct

import numpy as np

ITEM_BYTES = {"BF16": 2, "F32": 4, "U16": 2, "U8": 1}
NUMPY_VIEW = {"BF16": np.uint16, "F32": np.uint32, "U16": np.uint16,
              "U8": np.uint8}
JAX_DTYPE = {"BF16": "bfloat16", "F32": "float32", "U16": "uint16",
             "U8": "uint8"}
LAYER = "model.layers.1."


def tensor_table(c: dict) -> list[tuple[str, str, tuple[int, ...]]]:
    """(name, safetensors dtype, shape), name-sorted as the file stores
    them. ``c`` is the configuration file: a published config.json."""
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    kv_lora, routed = c["kv_lora_rank"], c["n_routed_experts"]
    expert = c["moe_intermediate_size"]
    rows = [
        ("model.embed_tokens.weight", "BF16", (c["vocab_size"], hidden)),
        (LAYER + "input_layernorm.weight", "BF16", (hidden,)),
        (LAYER + "post_attention_layernorm.weight", "BF16", (hidden,)),
        (LAYER + "self_attn.q_proj.weight", "BF16",
         (heads * (nope + rope), hidden)),
        (LAYER + "self_attn.kv_a_proj_with_mqa.weight", "BF16",
         (kv_lora + rope, hidden)),
        (LAYER + "self_attn.kv_a_layernorm.weight", "BF16", (kv_lora,)),
        (LAYER + "self_attn.kv_b_proj.weight", "BF16",
         (heads * (nope + v), kv_lora)),
        (LAYER + "self_attn.o_proj.weight", "BF16", (hidden, heads * v)),
        (LAYER + "mlp.gate.weight", "BF16", (routed, hidden)),
        (LAYER + "mlp.gate.e_score_correction_bias", "F32", (routed,)),
    ]
    mlps = [(f"mlp.experts.{e}.", expert) for e in range(routed)]
    mlps.append(("mlp.shared_experts.", c["n_shared_experts"] * expert))
    for prefix, width in mlps:
        rows += [
            (LAYER + prefix + "gate_proj.weight", "BF16", (width, hidden)),
            (LAYER + prefix + "up_proj.weight", "BF16", (width, hidden)),
            (LAYER + prefix + "down_proj.weight", "BF16", (hidden, width)),
        ]
    obj = c["object"]
    rows += [("zz.chipbench.random_bits.u16", "U16",
              (obj["probe_u16_items"],)),
             ("zz.chipbench.random_bits.u8", "U8", (obj["probe_u8_items"],))]
    return sorted(rows)


class Objects:
    """The one object of a configuration and seed. ``index`` is ignored:
    every operation pulls the same shard (under a new tag when cold)."""

    typed = True
    distinct = False

    def __init__(self, config: dict, seed: int):
        self.seed = seed
        self.widths = config
        self.tensors = tensor_table(config)
        self.spans: dict[str, tuple[int, int]] = {}
        self._expected: dict[str, np.ndarray] = {}
        header = {}
        at = 0
        for name, dtype, shape in self.tensors:
            size = int(np.prod(shape)) * ITEM_BYTES[dtype]
            header[name] = {"dtype": dtype, "shape": list(shape),
                            "data_offsets": [at, at + size]}
            self.spans[name] = (at, at + size)
            at += size
        raw = json.dumps(header, separators=(",", ":")).encode()
        # Trailing spaces are legal header padding; start the data 2 bytes
        # into a word, so every view is cut at an offset that is aligned
        # for bf16 and not for the sink's word buffer.
        raw += b" " * ((2 - (8 + len(raw))) % 4)
        self.head = struct.pack("<Q", len(raw)) + raw
        self.data_start = len(self.head)
        self.length = self.data_start + at

    def size(self, index: int = 0) -> int:
        return self.length

    def tensor_bytes(self, name: str) -> np.ndarray:
        """uint8 array of one tensor's bytes. Float tensors are finite
        normal numbers: every sign and mantissa bit random, the exponent's
        top three bits forced to 011 (magnitudes 2**-31..2**0), two
        passes over the words. The integer tensors are random bits."""
        index, (_, dtype, _) = next(
            (i, t) for i, t in enumerate(self.tensors) if t[0] == name)
        begin, end = self.spans[name]
        raw = np.random.PCG64([self.seed, index]).random_raw(
            (end - begin + 7) // 8)
        words = raw.view(np.uint32)
        if dtype == "BF16":
            words &= np.uint32(0x8FFF8FFF)
            words |= np.uint32(0x30003000)
        elif dtype == "F32":
            words &= np.uint32(0x8FFFFFFF)
            words |= np.uint32(0x30000000)
        return words.view(np.uint8)[: end - begin]

    def segments(self, index: int = 0):
        """The file as consecutive uint8 arrays: header, then tensors
        (made four at a time: the generator releases the GIL)."""
        yield np.frombuffer(self.head, np.uint8)
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            yield from pool.map(self.tensor_bytes,
                                [name for name, _, _ in self.tensors])

    # -- what the check samples ------------------------------------------

    def sample(self, rng: np.random.Generator, whole_first: bool):
        """[(tensor name, row slice or None)] to fetch back and compare:
        the last tensor by offset, one mid expert, the F32 bias, both
        integer tensors, two embedding rows; with ``whole_first`` also
        the first tensor by offset (the embedding, the largest)."""
        by_offset = sorted(self.spans, key=lambda n: self.spans[n][0])
        model = [n for n in by_offset if n.startswith("model.")]
        routed = self.widths["n_routed_experts"]
        vocab = self.widths["vocab_size"]
        expert = int(rng.integers(1, routed - 1))
        matrix = ("gate_proj", "up_proj", "down_proj")[int(rng.integers(3))]
        rows = sorted({0, vocab - 1, int(rng.integers(1, vocab - 1))})
        picks = [(model[0], slice(r, r + 1)) for r in rows]
        picks += [(model[-1], None),
                  (LAYER + f"mlp.experts.{expert}.{matrix}.weight", None),
                  (LAYER + "mlp.gate.e_score_correction_bias", None),
                  ("zz.chipbench.random_bits.u16", None),
                  ("zz.chipbench.random_bits.u8", None)]
        if whole_first:
            picks.append((model[0], None))
        return picks

    def expected(self, name: str, rows: slice | None) -> np.ndarray:
        """The plain reference: the generator's bytes parsed with
        ``numpy.frombuffer``, as rows of bytes."""
        _, dtype, shape = next(t for t in self.tensors if t[0] == name)
        if name not in self._expected:      # one object: made once a run
            flat = np.frombuffer(self.tensor_bytes(name), NUMPY_VIEW[dtype])
            self._expected[name] = flat.reshape(shape).view(
                np.uint8).reshape(shape[0], -1)
        want = self._expected[name]
        return want if rows is None else want[rows]

    def fetch(self, tensors: dict, rng: np.random.Generator,
              whole_first: bool) -> list:
        """Bring the sampled tensors to the host, with what the device
        says of each: (name, rows, (dtype, shape, devices), bytes)."""
        out = [("", None, None, sorted(tensors))]
        for name, rows in self.sample(rng, whole_first):
            t = tensors[name]
            meta = (str(t.dtype), tuple(t.shape), len(t.devices()))
            got = np.asarray(t if rows is None else t[rows])
            out.append((name, rows, meta,
                        got.view(np.uint8).reshape(got.shape[0], -1)))
        return out

    def matches(self, item) -> bool:
        name, rows, meta, got = item
        if not name:      # the set of names
            return got == sorted(n for n, _, _ in self.tensors)
        _, dtype, shape = next(t for t in self.tensors if t[0] == name)
        return (meta == (JAX_DTYPE[dtype], shape, 1)
                and np.array_equal(got, self.expected(name, rows)))
