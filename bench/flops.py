"""Operations and bytes of the measured work, computed from shapes.

Kept with the benchmark so that every PR counts the same work the same
way.  Counts are of the algorithm's useful work: padding rows and
recomputation are not counted.  What depends on the architecture is in
``archs/<arch>.py``.
"""
from __future__ import annotations

import importlib


def arch(name: str):
    """The module ``archs/<name>.py`` that counts an architecture's work
    (``unit_flops``, ``unit_conv_weights``, ``cost_layers``,
    ``PROGRAM_CLASS``), found by the configuration's ``model.arch``."""
    try:
        return importlib.import_module(f"bench.archs.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"bench.archs.{name}":
            raise KeyError(f"no bench/archs/{name}.py for architecture "
                           f"{name!r}") from None
        raise


# integer operations per element and bit plane of the fault hash:
# two lowbias32 mixes (3 xor-shifts of 2 ops + 2 multiplies = 8 each),
# the plane offset add and the seed xor, the shift, two casts, the
# float multiply, the compare and the select-or into the mask
BITFLIP_OPS_PER_PLANE = 2 * 8 + 2 + 1 + 2 + 1 + 1 + 2


def bitflip_cost(n_elems: int, faulty_bits: int = 4,
                 itemsize: int = 1) -> tuple[int, int]:
    """(operations, bytes) of one ``bitflip`` call on ``n_elems``
    integers: the hash per element and bit plane plus the index and the
    final xor, one read and one write of every element."""
    ops = n_elems * (faulty_bits * BITFLIP_OPS_PER_PLANE + 3)
    return ops, 2 * n_elems * itemsize
