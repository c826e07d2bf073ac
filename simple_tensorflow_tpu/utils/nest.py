"""stf.nest: structure flatten/pack utilities
(ref: tensorflow/python/util/nest.py — the public structure helpers TF
programs use everywhere).

Reference semantics, pinned exactly (where ``jax.tree_util`` — the
machinery the lowering itself uses — differs, the structural walk here
is done directly rather than delegated):

- ``None`` is an ATOM (a leaf), not an empty structure (jax's default
  treats None as an empty subtree),
- EVERY mapping flattens in ``sorted(keys)`` order — including
  OrderedDict and other dict subclasses, which jax flattens in
  insertion order (silently mispairing map_structure otherwise),
- namedtuples are structures and their type is preserved on packing;
  packing a mapping preserves its type and original key order,
- strings are atoms.

Conformance against the reference's documented behavior is pinned in
tests/test_nest.py.
"""

from __future__ import annotations

from typing import Any, List

__all__ = ["assert_same_structure", "flatten", "is_nested", "is_sequence",
           "map_structure", "pack_sequence_as"]


def _is_namedtuple(x) -> bool:
    return isinstance(x, tuple) and hasattr(x, "_fields")


def is_sequence(structure) -> bool:
    """True for list/tuple/dict/namedtuple — NOT for strings, numpy
    arrays, or Tensors (ref: nest.py ``is_sequence``)."""
    return isinstance(structure, (list, tuple, dict)) \
        and not isinstance(structure, str)


def is_nested(structure) -> bool:
    return is_sequence(structure)


def flatten(structure) -> List[Any]:
    """Flatten a (possibly nested) structure into a flat list of its
    atoms, mappings in sorted-key order; an atom flattens to ``[atom]``
    (ref: nest.py ``flatten``)."""
    out: List[Any] = []

    def rec(s):
        if not is_sequence(s):
            out.append(s)
        elif isinstance(s, dict):
            for k in sorted(s):
                rec(s[k])
        else:
            for x in s:
                rec(x)

    rec(structure)
    return out


def _sequence_like(instance, values):
    """Rebuild a structure of ``instance``'s type from child values
    (ref: nest.py ``_sequence_like``). For mappings, ``values`` arrive
    in sorted-key order and the result keeps the ORIGINAL key order."""
    if isinstance(instance, dict):
        by_key = dict(zip(sorted(instance), values))
        try:
            return type(instance)((k, by_key[k]) for k in instance)
        except TypeError:
            # dict subclass with a non-standard constructor
            # (e.g. defaultdict takes a factory first): plain dict
            return {k: by_key[k] for k in instance}
    if _is_namedtuple(instance):
        return type(instance)(*values)
    return type(instance)(values)


def pack_sequence_as(structure, flat_sequence):
    """Pack ``flat_sequence`` into the shape of ``structure``
    (ref: nest.py ``pack_sequence_as``). Raises ValueError when the
    lengths disagree."""
    flat = list(flat_sequence)
    if not is_sequence(structure):
        if len(flat) != 1:
            raise ValueError(
                f"Structure is a scalar but len(flat_sequence)="
                f"{len(flat)} > 1")
        return flat[0]
    it = iter(flat)

    def rec(s):
        if not is_sequence(s):
            try:
                return next(it)
            except StopIteration:
                raise ValueError(
                    f"Could not pack sequence: structure has more atoms "
                    f"than flat_sequence ({len(flat)}). "
                    f"Structure: {structure!r}.")
        if isinstance(s, dict):
            vals = [rec(s[k]) for k in sorted(s)]
        else:
            vals = [rec(x) for x in s]
        return _sequence_like(s, vals)

    packed = rec(structure)
    leftovers = sum(1 for _ in it)
    if leftovers:
        raise ValueError(
            f"Could not pack sequence: flat_sequence has {leftovers} "
            f"more atoms than the structure. Structure: {structure!r}.")
    return packed


def assert_same_structure(nest1, nest2, check_types: bool = True) -> None:
    """Raise ValueError when the two structures differ in shape, or
    TypeError when ``check_types`` and a substructure differs in type
    (list vs tuple, tuple vs namedtuple...) — reference nest.py
    semantics."""

    def rec(a, b):
        a_seq, b_seq = is_sequence(a), is_sequence(b)
        if a_seq != b_seq:
            raise ValueError(
                "The two structures don't have the same nested "
                f"structure: {nest1!r} vs {nest2!r}.")
        if not a_seq:
            return
        if check_types and type(a) is not type(b):
            # dict subclasses with equal keys pass (the reference only
            # enforces strict types on sequences/namedtuples)
            if not (isinstance(a, dict) and isinstance(b, dict)
                    and sorted(a) == sorted(b)):
                raise TypeError(
                    "The two structures don't have the same sequence "
                    f"type: {type(a).__name__} vs {type(b).__name__}.")
        if isinstance(a, dict):
            if sorted(a) != sorted(b):
                raise ValueError(
                    f"The two dictionaries don't have the same set of "
                    f"keys: {sorted(a)} vs {sorted(b)}.")
            for k in sorted(a):
                rec(a[k], b[k])
            return
        if len(a) != len(b):
            raise ValueError(
                "The two structures don't have the same number of "
                f"elements: {len(a)} vs {len(b)}.")
        for x, y in zip(a, b):
            rec(x, y)

    rec(nest1, nest2)


def map_structure(func: Callable, *structures, **kwargs):
    """Apply ``func`` atom-wise across structurally identical nests,
    returning a nest shaped like the first (ref: nest.py
    ``map_structure``)."""
    check_types = kwargs.pop("check_types", True)
    if kwargs:
        raise ValueError(f"Unknown keyword arguments: {list(kwargs)}")
    if not callable(func):
        raise TypeError(f"func must be callable, got {func!r}")
    if not structures:
        raise ValueError("Must provide at least one structure")
    for other in structures[1:]:
        assert_same_structure(structures[0], other,
                              check_types=check_types)
    flats = [flatten(s) for s in structures]
    results = [func(*atoms) for atoms in zip(*flats)]
    return pack_sequence_as(structures[0], results)
