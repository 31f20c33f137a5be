"""Object kind ``safetensors_layers``: one file of a checkpoint, holding
several whole MoE layers, of which an expert-parallel rank needs its share.

The file holds the layers ``object.layers`` of the configuration, each
``safetensors_shard.tensor_table``'s layer under its own index with ALL the
published routed experts (``n_routed_experts`` of the configuration is what
ONE rank holds; ``deployment.expert_parallel.ranks`` of them share a layer),
no ``embed_tokens`` and no probe tensors, name-sorted as safetensors files
store them, the data starting 2 bytes into a word. Weights are made as
``safetensors_shard`` makes them: a pure function of (seed, tensor index).

What a rank selects, and the plain reckoning of what a pull of it must
bring, also live here: rank r holds experts ``held * r .. held * r + held -
1`` of every layer and all of a layer that is not routed. Nothing here
imports the program under test or jax.
"""

from __future__ import annotations

import concurrent.futures
import json
import re
import struct

import numpy as np

from objects import safetensors_shard as shard

ROUTED = re.compile(r"\.mlp\.experts\.(\d+)\.")


def word_checksums(raw: np.ndarray) -> tuple[int, int]:
    """(sum32, xor32) of a tensor's bytes as little-endian uint32 words,
    zero-padded to a whole word: the plain form, as ``origin.py`` has it
    for pieces."""
    raw = np.ascontiguousarray(raw)
    if raw.size % 4:
        raw = np.concatenate([raw, np.zeros(-raw.size % 4, np.uint8)])
    words = raw.view("<u4")
    return (int(np.sum(words, dtype=np.uint64) & 0xFFFFFFFF),
            int(np.bitwise_xor.reduce(words)) if words.size else 0)


class Objects(shard.Objects):
    """The one file of a configuration and seed, and one rank's share of
    it. ``index`` is ignored: every operation pulls from the same file
    (under a new tag)."""

    typed = True
    distinct = False

    def __init__(self, config: dict, seed: int):
        self.seed = seed
        self.widths = config
        parallel = config["deployment"]["expert_parallel"]
        self.ranks, self.rank = int(parallel["ranks"]), int(parallel["rank"])
        self.held = int(config["n_routed_experts"])
        self.coalesce_gap = int(config["deployment"]["coalesce_gap"])
        self.prefix_guess = int(config["deployment"]["prefix_guess"])
        whole = {**config, "n_routed_experts": self.held * self.ranks,
                 "object": {"probe_u16_items": 0, "probe_u8_items": 0}}
        one_layer = [(name[len(shard.LAYER):], dtype, shape)
                     for name, dtype, shape in shard.tensor_table(whole)
                     if name.startswith(shard.LAYER)]
        self.tensors = sorted(
            (f"model.layers.{layer}.{rest}", dtype, shape)
            for layer in config["object"]["layers"]
            for rest, dtype, shape in one_layer)
        self.spans: dict[str, tuple[int, int]] = {}
        self._expected: dict[str, np.ndarray] = {}
        header = {}
        at = 0
        for name, dtype, shape in self.tensors:
            size = int(np.prod(shape)) * shard.ITEM_BYTES[dtype]
            header[name] = {"dtype": dtype, "shape": list(shape),
                            "data_offsets": [at, at + size]}
            self.spans[name] = (at, at + size)
            at += size
        raw = json.dumps(header, separators=(",", ":")).encode()
        # As safetensors_shard: the data starts 2 bytes into a word.
        raw += b" " * ((2 - (8 + len(raw))) % 4)
        self.head = struct.pack("<Q", len(raw)) + raw
        self.data_start = len(self.head)
        self.length = self.data_start + at

    # -- the rank's share --------------------------------------------------

    def selects(self, name: str, rank: int | None = None) -> bool:
        """Whether ``rank`` (the configuration's own by default) holds the
        tensor: its block of every layer's routed experts, and everything
        that is not routed."""
        routed = ROUTED.search(name)
        return routed is None or int(routed.group(1)) // self.held == (
            self.rank if rank is None else rank)

    def selector(self, rank: int | None = None):
        """``selector(name, meta)`` as ``download_sharded`` takes it."""
        return lambda name, meta: self.selects(name, rank)

    def selected(self, rank: int | None = None) -> list[str]:
        """The rank's tensors, in the file's order."""
        return [name for name, _, _ in self.tensors
                if self.selects(name, rank)]

    def size(self, index: int = 0) -> int:
        """The bytes of the rank's tensors: what an operation makes
        resident, and what the origin's bytes are held against."""
        return sum(self.spans[n][1] - self.spans[n][0]
                   for n in self.selected())

    def plan(self, rank: int | None = None
             ) -> tuple[list[str], list[tuple[int, int, list[str]]]]:
        """What a pull of the rank's tensors needs beside the header's
        ranged task of ``prefix_guess`` bytes, reckoned plainly from the
        header: (the tensors that lie whole inside that task, the spans
        [(start, end, names)] in absolute bytes that the others coalesce
        into where neighbours lie at most ``coalesce_gap`` apart)."""
        plen = min(self.prefix_guess, self.length)
        inside, spans = [], []
        for name in self.selected(rank):
            start, end = (self.data_start + at for at in self.spans[name])
            if end <= plen:
                inside.append(name)
            elif spans and start - spans[-1][1] <= self.coalesce_gap:
                spans[-1] = (spans[-1][0], end, spans[-1][2] + [name])
            else:
                spans.append((start, end, [name]))
        return inside, spans

    def rank_facts(self) -> dict:
        """What the check's first line compares with, in the shape of the
        origin's ``/facts``: here a "piece" is one of the rank's tensors (of
        no common size: ``piece_bytes`` 0), ``checksums`` their (sum32,
        xor32) in the file's order, ``length`` their bytes together."""
        names = self.selected()
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            sums = list(pool.map(
                lambda name: word_checksums(self.tensor_bytes(name)), names))
        return {"length": self.size(), "piece_bytes": 0, "checksums": sums}

    # -- what the check samples ------------------------------------------

    def sample(self, rng: np.random.Generator, whole_first: bool):
        """[(tensor name, None)] to fetch back whole and compare: always
        the rank's first and last tensor by offset, one cut from the
        header's ranged task (where any is), one of a smallest span and
        one of a largest, and one layer's F32 bias."""
        inside, spans = self.plan()
        names = self.selected()
        picks = [names[0], names[-1]]
        if inside:
            picks.append(inside[int(rng.integers(len(inside)))])
        by_size = sorted(spans, key=lambda s: s[1] - s[0])
        for span in (by_size[:1] + by_size[-1:]):
            picks.append(span[2][int(rng.integers(len(span[2])))])
        biases = [n for n in names if n.endswith("e_score_correction_bias")]
        picks.append(biases[int(rng.integers(len(biases)))])
        return [(name, None) for name in dict.fromkeys(picks)]

    def matches(self, item) -> bool:
        name, _, _, got = item
        if not name:      # the set of names: the rank's, and no other's
            return got == sorted(self.selected())
        return super().matches(item)
