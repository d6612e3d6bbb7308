"""Store integrity battery (DESIGN.md SS12): checksum primitives, the
fingerprint stamp/verify contract, and the fsck scan/heal cycle over a
real fleet store — truncated tile, bit-flipped tile, orphaned tile,
stale-fingerprint resume — each detected, reported in --json, and (where
healable) recomputed to byte-identical output by one fleet pass."""
import dataclasses
import json
import pathlib
import shutil

import numpy as np
import pytest

from repro.core.types import EDMConfig
from repro.data import store
from repro.inference import SignificanceConfig
from repro.launch import edm_fleet
from repro.runtime import integrity

ARTIFACTS = ("causal_map", "rho_conv", "rho_trend", "pvals", "edges")
CFG = EDMConfig(E_max=4, lib_block=4, target_tile=6)
SIG = SignificanceConfig(lib_sizes=(40, 80), n_surrogates=6, seed=0)


# ----------------------------------------------------------- primitives
def test_checksum_primitives(tmp_path):
    data = b"the store is the ground truth"
    assert integrity.checksum_bytes(data) == integrity.checksum_bytes(data)
    assert integrity.checksum_bytes(data) != integrity.checksum_bytes(data + b"!")
    f = tmp_path / "blob"
    f.write_bytes(data)
    assert integrity.checksum_file(f) == integrity.checksum_bytes(data)
    a = np.arange(20, dtype=np.float32).reshape(4, 5)
    # slab streaming must equal the one-shot hash
    assert integrity.checksum_ndarray(a) == \
        integrity.checksum_ndarray(a, rows_per_step=1) == \
        integrity.Crc32().update(a.tobytes()).hex
    # a memmap view hashes the same as the in-memory array
    np.save(tmp_path / "a.npy", a)
    mm = np.load(tmp_path / "a.npy", mmap_mode="r")
    assert integrity.checksum_ndarray(mm) == integrity.checksum_ndarray(a)


def test_atomic_save_records_matching_crc(tmp_path):
    a = np.random.default_rng(3).standard_normal((7, 9)).astype(np.float32)
    stats = store.atomic_save_npy(tmp_path / "a.npy", a)
    # the crc accumulated during the write equals a post-hoc file hash
    assert stats["crc32"] == integrity.checksum_file(tmp_path / "a.npy")


def test_sidecar_verify_and_load(tmp_path):
    a = np.ones((3, 3), np.float32)
    store.save_npy_checksummed(tmp_path / "a.npy", a)
    assert integrity.verify_file(tmp_path / "a.npy") == "ok"
    np.testing.assert_array_equal(
        integrity.load_npy_verified(tmp_path / "a.npy"), a)
    raw = bytearray((tmp_path / "a.npy").read_bytes())
    raw[-1] ^= 0xFF
    (tmp_path / "a.npy").write_bytes(bytes(raw))
    assert integrity.verify_file(tmp_path / "a.npy") == "corrupt"
    with pytest.raises(integrity.IntegrityError, match="checksum"):
        integrity.load_npy_verified(tmp_path / "a.npy")
    (tmp_path / "a.npy.crc32").unlink()
    assert integrity.verify_file(tmp_path / "a.npy") == "unverified"
    assert integrity.verify_file(tmp_path / "missing.npy") == "missing"


def test_manifest_self_checksum_roundtrip(tmp_path):
    entries = {"0,0": [3, 3, "aabbccdd"], "3": [3, "11223344"]}
    f = tmp_path / "blocks.json"
    f.write_text(integrity.manifest_with_crc(entries))
    assert integrity.read_manifest_shard(f) == entries
    # flip one byte inside an entry -> the shard fails its self-check
    f.write_text(f.read_text().replace("aabbccdd", "aabbccde"))
    assert integrity.read_manifest_shard(f) is None
    # torn JSON also reads as None, not an exception
    f.write_text('{"__crc__": "00000000", "0,0": [3,')
    assert integrity.read_manifest_shard(f) is None


def test_assemble_verifies_tile_checksums(tmp_path):
    N = 4
    w = store.TileWriter(tmp_path / "w", N)
    w.write_tile(0, 0, np.ones((2, N), np.float32))
    w.write_tile(2, 0, np.full((2, N), 2.0, np.float32))
    tf = tmp_path / "w" / "tile_00000002_00000000.npy"
    raw = bytearray(tf.read_bytes())
    raw[-2] ^= 0x20
    tf.write_bytes(bytes(raw))
    with pytest.raises(integrity.IntegrityError, match="fsck"):
        store.TileWriter(tmp_path / "w", N).assemble()


# ---------------------------------------------------------- fingerprint
def test_fingerprint_pins_data_and_config(tmp_path):
    ts = np.random.default_rng(0).standard_normal((6, 30)).astype(np.float32)
    fp = integrity.fingerprint_of(ts, CFG)
    assert fp == integrity.fingerprint_of(ts.copy(), CFG)
    assert fp != integrity.fingerprint_of(ts + 1, CFG)
    changed = integrity.fingerprint_of(
        ts, EDMConfig(E_max=5, lib_block=4, target_tile=6))
    assert fp["fingerprint"] != changed["fingerprint"]
    # byte-invisible geometry knobs are canonicalized OUT: a resume under
    # a different tile size or worker mesh is the SAME run
    geom = integrity.fingerprint_of(
        ts, EDMConfig(E_max=4, lib_block=2, target_tile=3))
    assert fp["fingerprint"] == geom["fingerprint"]

    integrity.stamp_fingerprint(tmp_path, fp)
    integrity.stamp_fingerprint(tmp_path, fp)  # idempotent
    with pytest.raises(integrity.IntegrityError, match="fingerprint"):
        integrity.stamp_fingerprint(tmp_path, integrity.fingerprint_of(ts + 1, CFG))


def test_default_config_fingerprint_pinned():
    """Stores stamped by earlier releases must stay resumable: the
    fingerprint of a fixed default config is pinned to the value those
    releases computed, so removing or renaming an EDMConfig field needs a
    canonicalisation rule in run_fingerprint."""
    fp = integrity.run_fingerprint(
        "0000abcd", (16, 300), "float32", dataclasses.asdict(EDMConfig()))
    assert fp == "caeed476c1de442c"


# ------------------------------------------------------ fleet store fsck
@pytest.fixture(scope="module")
def pristine(tmp_path_factory):
    """One complete single-worker fleet store; tests copy it, damage the
    copy, and compare healed recomputes byte-for-byte against it."""
    root = tmp_path_factory.mktemp("pristine")
    ts = np.random.default_rng(7).standard_normal((16, 250)).astype(np.float32)
    store.save_dataset(root / "dataset", ts, {"synthetic": "16x250"})
    out = root / "fleet"
    edm_fleet.init_fleet(out, root / "dataset", CFG, SIG)
    edm_fleet.FleetWorker(out, "w0", progress=False).run()
    rep = integrity.fsck_store(out)
    assert rep["clean"], json.dumps(rep, indent=1)
    return out


def _damaged_copy(pristine: pathlib.Path, dst_root: pathlib.Path) -> pathlib.Path:
    out = dst_root / "fleet"
    shutil.copytree(pristine, out)
    return out


def _bytes_of(out: pathlib.Path) -> dict:
    return {n: (out / n / "data.npy").read_bytes() for n in ARTIFACTS}


def _heal_and_recompute(out: pathlib.Path) -> None:
    rep = integrity.fsck_store(out, heal=True)
    assert "refused" not in rep["healed"]
    assert integrity.fsck_store(out)["clean"]
    edm_fleet.FleetWorker(out, "wheal", progress=False).run()
    assert integrity.fsck_store(out)["clean"]


@pytest.mark.parametrize("damage", ["truncate", "bitflip", "orphan",
                                    "delete", "sig_bitflip", "torn_shard"])
def test_fsck_detects_and_heals_byte_identical(pristine, tmp_path, damage):
    base = _bytes_of(pristine)
    out = _damaged_copy(pristine, tmp_path)
    tiles = sorted(out.glob("tile_*.npy"))
    if damage == "truncate":
        tiles[0].write_bytes(tiles[0].read_bytes()[:32])
        expect = ("phase2", "corrupt")
    elif damage == "bitflip":
        raw = bytearray(tiles[2].read_bytes())
        raw[len(raw) // 2] ^= 0x01
        tiles[2].write_bytes(bytes(raw))
        expect = ("phase2", "corrupt")
    elif damage == "orphan":
        (out / "tile_99999999_00000000.npy").write_bytes(b"\x93NUMPY junk")
        expect = ("phase2", "orphaned")
    elif damage == "delete":
        tiles[1].unlink()
        expect = ("phase2", "missing")
    elif damage == "sig_bitflip":
        st = sorted((out / "pvals").glob("tile_*.npy"))[0]
        raw = bytearray(st.read_bytes())
        raw[-3] ^= 0x80
        st.write_bytes(bytes(raw))
        expect = ("pvals", "corrupt")
    else:  # torn_shard
        shard = next(out.glob("blocks*.json"))
        shard.write_text(shard.read_text()[:25])
        expect = ("phase2", "torn_shards")

    rep = integrity.fsck_store(out)
    art, kind = expect
    assert not rep["clean"]
    assert rep["artifacts"][art][kind], json.dumps(rep, indent=1)
    _heal_and_recompute(out)
    assert _bytes_of(out) == base  # recomputed units are byte-identical


def test_fsck_heals_corrupt_assembled_map(pristine, tmp_path):
    base = _bytes_of(pristine)
    out = _damaged_copy(pristine, tmp_path)
    f = out / "causal_map" / "data.npy"
    raw = bytearray(f.read_bytes())
    raw[-5] ^= 0x04
    f.write_bytes(bytes(raw))
    rep = integrity.fsck_store(out)
    assert not rep["clean"]
    assert rep["artifacts"]["causal_map"]["status"] == "corrupt"
    _heal_and_recompute(out)
    assert _bytes_of(out) == base


def test_fsck_stale_fingerprint_refuses_heal(pristine, tmp_path):
    out = _damaged_copy(pristine, tmp_path)
    # swap the dataset content in place: same path, different bytes
    ds = pathlib.Path(json.loads((out / "fleet.json").read_text())["dataset"])
    ts = np.asarray(store.load_dataset(ds), np.float32)
    store.save_dataset(ds, ts + 0.5)
    try:
        rep = integrity.fsck_store(out, heal=True)
        assert rep["fingerprint"]["status"] == "stale"
        assert not rep["clean"]
        assert "refused" in rep["healed"]
        # a worker joining against the swapped dataset is refused too
        with pytest.raises(integrity.IntegrityError, match="fingerprint"):
            edm_fleet.FleetWorker(out, "wjoin", progress=False)
    finally:  # module-scoped pristine shares this dataset — restore it
        store.save_dataset(ds, ts)


def test_fsck_cli_json_and_exit_codes(pristine, tmp_path, capsys):
    out = _damaged_copy(pristine, tmp_path)
    edm_fleet.main(["fsck", "--out", str(out), "--json", "--expect-clean"])
    rep = json.loads(capsys.readouterr().out)
    assert rep["clean"] and rep["problems"] == 0
    # damage -> --expect-clean exits 1 and the report names the tile
    bad = sorted(out.glob("tile_*.npy"))[0]
    bad.write_bytes(bad.read_bytes()[:16])
    with pytest.raises(SystemExit) as ei:
        edm_fleet.main(["fsck", "--out", str(out), "--json", "--expect-clean"])
    assert ei.value.code == 1
    rep = json.loads(capsys.readouterr().out)
    assert bad.name in rep["artifacts"]["phase2"]["corrupt"]
    # --heal through the CLI, then a fleet pass -> clean and identical
    edm_fleet.main(["fsck", "--out", str(out), "--heal"])
    capsys.readouterr()
    edm_fleet.FleetWorker(out, "wcli", progress=False).run()
    edm_fleet.main(["fsck", "--out", str(out), "--expect-clean"])
    assert _bytes_of(out) == _bytes_of(pristine)
