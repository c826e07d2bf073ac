"""stf.nest conformance against reference tensorflow/python/util/nest.py
semantics: flatten order, dict key sorting,
namedtuple preservation, None-as-atom, error types."""

import collections

import numpy as np
import pytest

import simple_tensorflow_tpu as stf

nest = stf.nest

Point = collections.namedtuple("Point", ["x", "y"])


class TestFlatten:
    def test_atom_flattens_to_singleton(self):
        assert nest.flatten(5) == [5]
        assert nest.flatten("abc") == ["abc"]

    def test_none_is_an_atom(self):
        # reference nest: flatten(None) == [None]; jax's default treats
        # None as an empty subtree — stf.nest pins the reference behavior
        assert nest.flatten(None) == [None]
        assert nest.flatten([1, None, 2]) == [1, None, 2]

    def test_nested_list_tuple(self):
        assert nest.flatten([[1, 2], (3, [4])]) == [1, 2, 3, 4]

    def test_dict_sorted_key_order(self):
        # reference nest flattens dicts in sorted-key order
        assert nest.flatten({"b": 2, "a": 1, "c": 3}) == [1, 2, 3]

    def test_namedtuple(self):
        assert nest.flatten(Point(x=1, y=[2, 3])) == [1, 2, 3]

    def test_mixed_deep(self):
        s = {"w": Point(1, (2,)), "a": [3, {"z": 4, "y": 5}]}
        assert nest.flatten(s) == [3, 5, 4, 1, 2]

    def test_ordereddict_flattens_sorted_not_insertion(self):
        # reference nest sorts keys for EVERY mapping; jax.tree_util
        # flattens OrderedDict in insertion order — pinned here so
        # map_structure can never silently mispair atoms (r1 review fix)
        od = collections.OrderedDict([("b", 1), ("a", 2)])
        assert nest.flatten(od) == [2, 1]
        assert nest.flatten({"b": 1, "a": 2}) == [2, 1]

    def test_ordereddict_map_structure_pairs_by_key(self):
        od = collections.OrderedDict([("b", 1), ("a", 2)])
        out = nest.map_structure(lambda x, y: x + y, od,
                                 {"a": 10, "b": 20})
        assert dict(out) == {"a": 12, "b": 21}
        assert isinstance(out, collections.OrderedDict)
        assert list(out.keys()) == ["b", "a"]  # original order kept

    def test_defaultdict_packs_without_crashing(self):
        dd = collections.defaultdict(list, {"b": 1, "a": 2})
        flat = nest.flatten(dd)
        assert flat == [2, 1]
        packed = nest.pack_sequence_as(dd, [20, 10])
        assert dict(packed) == {"a": 20, "b": 10}


class TestPackSequenceAs:
    def test_roundtrip(self):
        for s in ([1, [2, 3]], (1, 2), {"a": 1, "b": (2, 3)},
                  Point(1, [2, 3]), 7):
            flat = nest.flatten(s)
            assert nest.pack_sequence_as(s, flat) == s

    def test_namedtuple_type_preserved(self):
        packed = nest.pack_sequence_as(Point(0, 0), [10, 20])
        assert isinstance(packed, Point)
        assert packed == Point(10, 20)

    def test_scalar_structure(self):
        assert nest.pack_sequence_as("ignored", [42]) == 42
        with pytest.raises(ValueError):
            nest.pack_sequence_as(5, [1, 2])

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError):
            nest.pack_sequence_as([1, 2], [1, 2, 3])


class TestMapStructure:
    def test_single(self):
        assert nest.map_structure(lambda x: x * 2, [1, (2, {"a": 3})]) \
            == [2, (4, {"a": 6})]

    def test_multi(self):
        out = nest.map_structure(lambda a, b: a + b,
                                 {"a": 1, "b": [2, 3]},
                                 {"a": 10, "b": [20, 30]})
        assert out == {"a": 11, "b": [22, 33]}

    def test_structure_mismatch_raises(self):
        with pytest.raises(ValueError):
            nest.map_structure(lambda a, b: a, [1, 2], [1, [2, 3]])

    def test_type_mismatch_raises_typeerror(self):
        with pytest.raises(TypeError):
            nest.map_structure(lambda a, b: a, [1, 2], (1, 2))

    def test_check_types_false_allows_list_vs_tuple(self):
        out = nest.map_structure(lambda a, b: a + b, [1, 2], (10, 20),
                                 check_types=False)
        assert out == [11, 22]

    def test_non_callable_raises(self):
        with pytest.raises(TypeError):
            nest.map_structure("not-a-fn", [1])


class TestAssertSameStructure:
    def test_ok(self):
        nest.assert_same_structure([1, {"a": (2,)}], [9, {"a": (8,)}])

    def test_shape_mismatch(self):
        with pytest.raises(ValueError):
            nest.assert_same_structure([1, 2, 3], [1, 2])

    def test_dict_key_mismatch(self):
        with pytest.raises(ValueError):
            nest.assert_same_structure({"a": 1}, {"b": 1})

    def test_namedtuple_vs_tuple(self):
        with pytest.raises(TypeError):
            nest.assert_same_structure(Point(1, 2), (1, 2))
        nest.assert_same_structure(Point(1, 2), (1, 2),
                                   check_types=False)


class TestIsSequence:
    def test_values(self):
        assert nest.is_sequence([1])
        assert nest.is_sequence((1,))
        assert nest.is_sequence({"a": 1})
        assert nest.is_sequence(Point(1, 2))
        assert not nest.is_sequence("abc")
        assert not nest.is_sequence(1)
        assert not nest.is_sequence(np.zeros(3))
        assert not nest.is_sequence(None)

    def test_is_nested_alias(self):
        assert nest.is_nested([1]) and not nest.is_nested(3)


def test_works_with_tensors():
    stf.reset_default_graph()
    a = stf.constant([1.0, 2.0])
    b = stf.constant([3.0, 4.0])
    s = {"p": a, "q": [b, a]}
    flat = nest.flatten(s)
    assert len(flat) == 3 and all(hasattr(t, "dtype") for t in flat)
    doubled = nest.map_structure(lambda t: t * 2.0, s)
    with stf.Session() as sess:
        out = sess.run(doubled)
    np.testing.assert_allclose(out["p"], [2.0, 4.0])
    np.testing.assert_allclose(out["q"][0], [6.0, 8.0])
