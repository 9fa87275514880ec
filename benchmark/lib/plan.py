"""The seeded plan: seed -> objects, the duplicate count they must produce
and the bytes of any block. The benchmark's own copy of `chip_smoke.py`'s
plan (`make_plan`, `block_bytes`), with every size a parameter so that a
configuration file states them. Imports nothing of the program.

Every seed gives the same sizes: `big_objects` objects of `object_blocks`
full blocks each, then `files` small files of `file_bytes` (each an object of
one block of its own size; a volume that states none has none), then the
ragged handful. Only which blocks repeat a pool entry — and the bytes —
change with the seed. Full blocks repeat one of `pool_blocks` whole-block
contents, files one of `file_pool_blocks` contents of `file_bytes`: a
duplicate has the size of what it repeats.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class PlannedBlock:
    """One block of one object. Two blocks are duplicates exactly when
    their `content` ids are equal."""
    # ("pool", i) | ("pool", file_bytes, i) | ("fresh", object_index, block_index)
    content: tuple
    size: int


@dataclasses.dataclass(frozen=True)
class PlannedObject:
    name: str
    blocks: tuple[PlannedBlock, ...]

    @property
    def size(self) -> int:
        return sum(b.size for b in self.blocks)


@dataclasses.dataclass(frozen=True)
class Plan:
    seed: int
    objects: tuple[PlannedObject, ...]

    @property
    def blocks(self) -> list[PlannedBlock]:
        return [b for o in self.objects for b in o.blocks]

    @property
    def nbytes(self) -> int:
        return sum(o.size for o in self.objects)

    @property
    def expected_duplicates(self) -> int:
        """Blocks whose content appeared earlier in the volume."""
        blocks = self.blocks
        return len(blocks) - len({b.content for b in blocks})


def make_plan(seed: int, big_objects: int, *, block: int = 4 << 20,
              object_blocks: int = 16, pool_blocks: int = 4,
              dup_probability: float = 0.3,
              ragged_sizes=(1, 100_001, (4 << 20) - 1, (4 << 20) + 7),
              files: int = 0, file_bytes: int = 0,
              file_pool_blocks: int = 0) -> Plan:
    if files and not (0 < file_bytes <= block and file_pool_blocks > 0):
        raise ValueError(f"a file of {file_bytes} B from a pool of "
                         f"{file_pool_blocks} is not one block of {block}")
    rng = np.random.default_rng([seed, 0])
    objects = []
    for o in range(big_objects):
        blocks = []
        for b in range(object_blocks):
            if rng.random() < dup_probability:
                content = ("pool", int(rng.integers(pool_blocks)))
            else:
                content = ("fresh", o, b)
            blocks.append(PlannedBlock(content, block))
        objects.append(PlannedObject(f"big-{o:04d}", tuple(blocks)))
    for f in range(files):
        if rng.random() < dup_probability:
            # the files' pool is named by their size: ("pool", i) is a whole
            # block's content and never a file's
            content = ("pool", file_bytes, int(rng.integers(file_pool_blocks)))
        else:
            content = ("fresh", big_objects + f, 0)
        objects.append(PlannedObject(
            f"file-{f:05d}", (PlannedBlock(content, file_bytes),)))
    for k, size in enumerate(ragged_sizes):
        o = big_objects + files + k
        sizes = [block] * (size // block) + ([size % block] if size % block else [])
        objects.append(PlannedObject(
            f"ragged-{size}",
            tuple(PlannedBlock(("fresh", o, b), s) for b, s in enumerate(sizes)),
        ))
    return Plan(seed, tuple(objects))


def block_bytes(seed: int, block: PlannedBlock) -> bytes:
    kind, *ids = block.content
    rng = np.random.default_rng([seed, 1 if kind == "pool" else 2, *ids])
    return rng.bytes(block.size)


def plan_of(seed: int, volume: dict) -> Plan:
    """The plan a configuration file's `volume` section states."""
    return make_plan(
        seed, volume["big_objects"], block=volume["block_bytes"],
        object_blocks=volume["object_blocks"], pool_blocks=volume["pool_blocks"],
        dup_probability=volume["dup_probability"],
        ragged_sizes=tuple(volume["ragged_sizes"]),
        files=volume.get("files", 0), file_bytes=volume.get("file_bytes", 0),
        file_pool_blocks=volume.get("file_pool_blocks", 0))
