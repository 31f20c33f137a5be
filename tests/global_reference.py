"""What "a checkpoint file sharded over the chips of one host" has to mean,
in plain numpy: from the file's bytes and a map of tensor name -> sharding,
every shard that every chip must hold
(``reference[sharding.devices_indices_map(shape)[chip]]`` of the tensor parsed
with ``np.frombuffer`` at the header's own offsets: ``placement_reference.
tensors``), and, for a map that gives
each tensor either one chip or all of them (four-way expert parallelism), the
plan a pull by destination has to make: which byte ranges go to which chips.

It imports nothing of the program (no ``ops/``, no ``client/``) and no jax:
a sharding is whatever has ``devices_indices_map``. The tests in
``test_global_landing.py`` hold the program to it.
"""

from __future__ import annotations

import json
import re

from tests.placement_reference import tensors

ROUTED = re.compile(r"\.mlp\.experts\.(\d+)\.")


def header_of(content: bytes) -> tuple[dict, int]:
    """(the JSON header, the offset at which the data starts)."""
    n = int.from_bytes(content[:8], "little")
    return json.loads(content[8:8 + n]), 8 + n


def shards(content: bytes, shardings: dict) -> dict[str, dict]:
    """name -> {device: the shard that device must hold}."""
    whole = tensors(content)
    return {name: {device: whole[name][index] for device, index in
                   sharding.devices_indices_map(whole[name].shape).items()}
            for name, sharding in shardings.items()}


def chip_of(name: str, experts_a_chip: int) -> int | None:
    """Under contiguous expert blocks: the one chip that keeps a routed
    expert's tensor, or None for a tensor that every chip keeps."""
    routed = ROUTED.search(name)
    return None if routed is None else int(routed.group(1)) // experts_a_chip


def plan(content: bytes, experts_a_chip: int, chips: int,
         prefix_guess: int) -> tuple[list[str], list[tuple]]:
    """What a pull by destination needs beside the header's ranged task of
    ``prefix_guess`` bytes: (the tensors that lie whole inside that task,
    [(start, end, chips that want it, names)] in absolute bytes: neighbours
    that touch merge where the same chips want them, and a range that
    begins inside the header's task is pulled whole)."""
    header, data_start = header_of(content)
    plen = min(prefix_guess, len(content))
    rows = sorted((data_start + meta["data_offsets"][0],
                   data_start + meta["data_offsets"][1], name)
                  for name, meta in header.items() if name != "__metadata__")
    inside: list[str] = []
    by_chips: dict[tuple, list] = {}
    for start, end, name in rows:
        chip = chip_of(name, experts_a_chip)
        wants = tuple(range(chips)) if chip is None else (chip,)
        if end <= plen:
            inside.append(name)
            continue
        spans = by_chips.setdefault(wants, [])
        if spans and start <= spans[-1][1]:
            spans[-1][1] = end
            spans[-1][3].append(name)
        else:
            spans.append([start, end, wants, [name]])
    return inside, sorted(tuple(s) for spans in by_chips.values()
                          for s in spans)
