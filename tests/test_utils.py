"""Utils: range sets, id allocation, hashing (ref: pkg/channeld/util_test.go)."""

from channeld_tpu.utils.idalloc import IdAllocator, difference, hash_string
from channeld_tpu.utils.ranges import RangeSet


def test_rangeset_parse_single_and_span():
    rs = RangeSet.parse("1")
    assert 1 in rs and 0 not in rs and 2 not in rs

    rs = RangeSet.parse("2-65535")
    assert 2 in rs and 65535 in rs and 1 not in rs and 65536 not in rs


def test_rangeset_multi_and_merge():
    rs = RangeSet.parse("1,3-5,4-8,10")
    assert [r for r in rs.ranges] == [(1, 1), (3, 8), (10, 10)]
    for v, expect in [(1, True), (2, False), (3, True), (8, True), (9, False), (10, True)]:
        assert (v in rs) == expect


def test_rangeset_empty():
    rs = RangeSet.parse("")
    assert not rs and 0 not in rs


def test_id_allocator_wraparound():
    alloc = IdAllocator(1, 3)
    used: set[int] = set()
    occ = used.__contains__
    assert alloc.next_id(occ) == 1
    used.add(1)
    assert alloc.next_id(occ) == 2
    used.add(2)
    assert alloc.next_id(occ) == 3
    used.add(3)
    # Full -> None
    assert alloc.next_id(occ) is None
    # Free one -> wraps around to reuse it
    used.remove(2)
    assert alloc.next_id(occ) == 2


def test_hash_string_stable():
    assert hash_string("alice") == hash_string("alice")
    assert hash_string("alice") != hash_string("bob")
    assert 0 <= hash_string("x") <= 0xFFFFFFFF


def test_difference():
    assert difference([1, 2, 3, 4], [2, 4, 5]) == [1, 3]


# ---- utils/devices.py: where the compile cache lives ------------------------


def test_compile_cache_sets_no_directory_when_the_variable_is_set(monkeypatch):
    import os
    import sys
    import types

    from channeld_tpu.utils.devices import place_compile_cache

    updates = []
    fake_jax = types.SimpleNamespace(config=types.SimpleNamespace(
        update=lambda name, value: updates.append(name)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    # (restored at teardown: the not-yet-imported path writes it)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    for loaded in (fake_jax, None):  # jax imported already / not yet
        monkeypatch.setitem(sys.modules, "jax", loaded)
        place_compile_cache()
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/placed/from/outside"
    assert updates == ["jax_persistent_cache_min_compile_time_secs"]


def test_compile_cache_default_is_the_checkout_from_any_directory(tmp_path):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import os; from channeld_tpu.utils.devices import "
        "place_compile_cache; place_compile_cache(); "
        "print(os.environ['JAX_COMPILATION_CACHE_DIR'], "
        "os.environ['JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS'])"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = repo
    seen = set()
    for name in ("a", "b"):
        cwd = tmp_path / name
        cwd.mkdir()
        seen.add(subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env,
            capture_output=True, text=True, check=True,
        ).stdout.strip())
    assert seen == {os.path.join(repo, ".jax_cache") + " 0"}
