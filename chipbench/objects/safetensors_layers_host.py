"""Object kind ``safetensors_layers_host``: the file of ``safetensors_layers``,
byte for byte for a seed, as ONE host keeps it whose chips are the ranks of an
expert-parallel group: ``n_routed_experts`` of the configuration is the
published count, all of which the host holds, ``deployment.expert_parallel.
ranks`` of its chips share a layer, chip r keeping experts ``held * r ..
held * r + held - 1`` of every layer (``held`` = experts / chips) and every
chip a copy of all that is not routed.

The plain reference of that layout also lives here: a tensor is the
generator's bytes parsed with ``numpy.frombuffer`` at the header's offsets
(``expected``), and the shard a chip must hold is ``reference[index]`` for the
index that the tensor's sharding gives that chip (``sharding.
devices_indices_map(shape)[chip]``, which the driver hands over: this module
imports neither jax nor the program under test, and the origin child loads it
too).
"""

from __future__ import annotations

import concurrent.futures

import numpy as np

from objects import safetensors_layers as layers
from objects import safetensors_shard as shard


class Objects(layers.Objects):
    """The one file of a configuration and seed, and where each tensor of it
    belongs on the host."""

    def __init__(self, config: dict, seed: int):
        deployment = config["deployment"]
        self.chips = int(deployment["expert_parallel"]["ranks"])
        held = int(config["n_routed_experts"]) // self.chips
        # The generator reads the experts ONE rank holds and the number of
        # ranks; the file it makes holds all of them.
        super().__init__({**config, "n_routed_experts": held, "deployment": {
            **deployment, "coalesce_gap": 0,
            "expert_parallel": {"ranks": self.chips, "rank": 0}}}, seed)
        self.widths = config

    # -- the layout ---------------------------------------------------------

    def chip_of(self, name: str) -> int | None:
        """The one chip that keeps a routed expert's tensor; None for a
        tensor that every chip keeps."""
        routed = layers.ROUTED.search(name)
        return None if routed is None else int(routed.group(1)) // self.held

    def holders(self, name: str) -> list[int]:
        chip = self.chip_of(name)
        return list(range(self.chips)) if chip is None else [chip]

    def selected(self, rank: int | None = None) -> list[str]:
        """Every tensor of the file: the host keeps them all."""
        return [name for name, _, _ in self.tensors]

    def size(self, index: int = 0) -> int:
        """The file: what the origin's bytes are held against."""
        return self.length

    def tensor_bytes_once(self) -> int:
        """The file's tensors counted once: what an operation lands."""
        return self.length - self.data_start

    def resident_bytes(self) -> list[int]:
        """What each chip holds at the end of an operation."""
        out = [0] * self.chips
        for name, _, _ in self.tensors:
            for chip in self.holders(name):
                out[chip] += self.spans[name][1] - self.spans[name][0]
        return out

    # -- the plain reference --------------------------------------------------

    def shard(self, name: str, index: tuple) -> np.ndarray:
        """``reference[index]``: the shard of a tensor that a sharding gives
        one chip, as the tensor's items (BF16 as uint16 bit patterns)."""
        _, dtype, shape = next(t for t in self.tensors if t[0] == name)
        flat = np.frombuffer(self.tensor_bytes(name), shard.NUMPY_VIEW[dtype])
        return flat.reshape(shape)[index]

    def shard_facts(self, indices: dict) -> dict:
        """What the check's first line compares with, in the shape of the
        origin's ``/facts``: a "piece" is one addressable shard, in the order
        of (name, chip); ``indices`` maps name -> {chip: index}. A tensor
        that several chips hold under the same index is summed once."""
        rows = [(name, chip, index) for name in sorted(indices)
                for chip, index in sorted(indices[name].items())]

        def one(name: str) -> dict:
            out = {}
            for index in {repr(i): i for i in indices[name].values()
                          }.values():
                raw = np.ascontiguousarray(self.shard(name, index))
                out[repr(index)] = layers.word_checksums(
                    raw.view(np.uint8).reshape(-1))
            return out

        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            sums = dict(zip(sorted(indices), pool.map(one, sorted(indices))))
        return {"length": self.tensor_bytes_once(), "piece_bytes": 0,
                "checksums": [sums[name][repr(index)]
                              for name, _, index in rows]}

    # -- what the check samples ------------------------------------------

    def sample(self, rng: np.random.Generator, whole_first: bool):
        """[(tensor name, chip)] to fetch back whole from that chip and
        compare: of each chip its first and last expert tensor by offset and
        one drawn from between, and its copy of one tensor that every chip
        keeps (the first, cut from the header's ranged task, for chip 0; an
        F32 router bias; two drawn)."""
        by_offset = sorted(self.spans, key=lambda n: self.spans[n][0])
        rest = [n for n in by_offset if self.chip_of(n) is None]
        biases = [n for n in rest if n.endswith("e_score_correction_bias")]
        shared = [rest[0], biases[int(rng.integers(len(biases)))],
                  rest[int(rng.integers(len(rest)))],
                  rest[int(rng.integers(len(rest)))]]
        picks = []
        for chip in range(self.chips):
            own = [n for n in by_offset if self.chip_of(n) == chip]
            picks += [(own[0], chip), (own[-1], chip),
                      (own[int(rng.integers(1, len(own) - 1))], chip),
                      (shared[chip % len(shared)], chip)]
        return picks

    def fetch(self, tensors: dict, rng: np.random.Generator,
              whole_first: bool, chip_of_device: dict | None = None) -> list:
        """Bring the sampled shards to the host, each from the chip that the
        sample names: (name, chip, (dtype, shape, chips that hold the
        tensor), bytes); first the set of names and where every tensor's
        shards lie. ``chip_of_device`` maps a device to its index on the
        host."""
        placed = {name: sorted(chip_of_device[s.device]
                               for s in t.addressable_shards)
                  for name, t in tensors.items()}
        out = [("", None, None, sorted(tensors)),
               ("@placement", None, None, placed)]
        for name, chip in self.sample(rng, whole_first):
            t = tensors[name]
            held = [s.data for s in t.addressable_shards
                    if chip_of_device[s.device] == chip]
            if len(held) != 1:
                out.append((name, chip, None, None))
                continue
            got = np.asarray(held[0])
            out.append((name, chip,
                        (str(got.dtype), tuple(got.shape), placed[name]),
                        got.view(np.uint8).reshape(got.shape[0], -1)))
        return out

    def matches(self, item) -> bool:
        name, chip, meta, got = item
        if not name:                # the set of names: the file's
            return got == sorted(n for n, _, _ in self.tensors)
        if name == "@placement":    # each shard's device against the plan
            return got == {n: self.holders(n) for n, _, _ in self.tensors}
        if name == "@store_bytes":  # read back once an operation: the file
            return self.length <= got <= self.length + self.prefix_guess
        if meta is None:
            return False
        _, dtype, shape = next(t for t in self.tensors if t[0] == name)
        return (meta == (shard.JAX_DTYPE[dtype], shape, self.holders(name))
                and np.array_equal(got, self.expected(name, None)))
