"""Fused libfm→ELL kernel parity: native/fastparse.cc dmlc_parse_libfm_ell
vs LibFMParser → FixedShapeBatcher('ell') composed (reference hot path
libfm_parser.h:67-144). The fused and generic batch streams must agree
bit-for-bit on labels/weights/indices/values/nnz/truncation across
dtypes, indexing modes, junk tokens, and sharding."""

import numpy as np
import pytest

from dmlc_core_tpu.data import create_parser, native
from dmlc_core_tpu.staging import BatchSpec, FixedShapeBatcher, ell_batches

fused = pytest.mark.skipif(
    not native.HAS_LIBFM_ELL, reason="native fused libfm kernel not built"
)


def _write_libfm(path, rows=400, k_max=6, one_based=False, seed=0,
                 junk=False):
    rng = np.random.default_rng(seed)
    lo = 1 if one_based else 0
    lines = []
    for i in range(rows):
        k = int(rng.integers(1, k_max + 1))
        toks = [f"{i % 2}" if i % 3 else f"{i % 2}:{0.5 + (i % 5)}"]
        for _ in range(k):
            fid = int(rng.integers(lo, 12))
            feat = int(rng.integers(lo, 500))
            if rng.random() < 0.5:
                toks.append(f"{fid}:{feat}:{rng.normal():.4f}")
            else:
                toks.append(f"{fid}:{feat}")
        if junk and i % 7 == 0:
            toks.append("noise")          # no colon: skipped
            toks.append("a:b:c")          # malformed numbers: skipped
            toks.append("3:4:5:6")        # extra colon: skipped
        lines.append(" ".join(toks))
    if junk:
        lines.insert(5, "not_a_label 1:2:3")  # bad label: line skipped
        lines.insert(9, "")                    # blank line
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path


def _spec(value_dtype="float32", B=64, K=4):
    return BatchSpec(
        batch_size=B, layout="ell", max_nnz=K,
        value_dtype=np.dtype(value_dtype),
    )


def _generic(path, spec, part_index=0, num_parts=1, indexing_mode=0):
    parser = create_parser(
        f"{path}?indexing_mode={indexing_mode}", part_index, num_parts,
        type="libfm", threaded=False,
    )
    batcher = FixedShapeBatcher(spec)
    out = list(batcher.batches(iter(parser)))
    parser.close()
    return out, batcher.truncated_nnz


def _fused(path, spec, part_index=0, num_parts=1, indexing_mode=0):
    from dmlc_core_tpu.staging import FusedEllLibFMBatches

    stream = FusedEllLibFMBatches(
        path, spec, part_index, num_parts, indexing_mode=indexing_mode
    )
    out = [
        type(b)(
            labels=b.labels.copy(), weights=b.weights.copy(),
            n_valid=b.n_valid, indices=b.indices.copy(),
            values=b.values.copy(), nnz=b.nnz.copy(),
        )
        for b in stream
    ]
    tr = stream.truncated_nnz
    stream.close()
    return out, tr


def _assert_equal(fb, gb):
    assert len(fb) == len(gb)
    for f, g in zip(fb, gb):
        assert f.n_valid == g.n_valid
        np.testing.assert_array_equal(f.labels, g.labels)
        np.testing.assert_array_equal(f.weights, g.weights)
        np.testing.assert_array_equal(f.nnz, g.nnz)
        np.testing.assert_array_equal(f.indices, g.indices)
        np.testing.assert_array_equal(f.values, g.values)


@fused
@pytest.mark.parametrize("value_dtype", ["float32", "float16"])
def test_fused_matches_generic(tmp_path, value_dtype):
    path = _write_libfm(str(tmp_path / "d.libfm"), rows=500, k_max=7)
    f, ft = _fused(path, _spec(value_dtype))
    g, gt = _generic(path, _spec(value_dtype))
    _assert_equal(f, g)
    assert ft == gt and ft > 0  # k_max 7 > K=4 → truncation exercised


@fused
def test_fused_matches_generic_with_junk_tokens(tmp_path):
    path = _write_libfm(str(tmp_path / "j.libfm"), rows=300, junk=True)
    f, ft = _fused(path, _spec())
    g, gt = _generic(path, _spec())
    _assert_equal(f, g)
    assert ft == gt


@fused
def test_one_based_indexing_modes(tmp_path):
    path = _write_libfm(str(tmp_path / "o.libfm"), rows=200, one_based=True)
    f, _ = _fused(path, _spec(), indexing_mode=1)
    g, _ = _generic(path, _spec(), indexing_mode=1)
    _assert_equal(f, g)
    # auto mode resolves 1-based from the head probe = explicit mode 1
    a, _ = _fused(path, _spec(), indexing_mode=-1)
    _assert_equal(a, f)
    # 1-based data under mode 1 never produces feature id -1: wrapped ids
    # are zeroed + counted, never negative
    assert all(int(b.indices.min()) >= 0 for b in f)


@fused
def test_sharded_exact_cover(tmp_path):
    path = _write_libfm(str(tmp_path / "s.libfm"), rows=400)
    labels = []
    for part in range(3):
        batches, _ = _fused(path, _spec(B=32), part_index=part, num_parts=3)
        for b in batches:
            labels.extend(b.labels[: b.n_valid].tolist())
    assert len(labels) == 400
    full, _ = _generic(path, _spec(B=400))
    np.testing.assert_array_equal(
        np.sort(np.asarray(labels)), np.sort(full[0].labels[:400])
    )


@fused
def test_dispatcher_routes_libfm(tmp_path):
    from dmlc_core_tpu.staging import FusedEllLibFMBatches
    from dmlc_core_tpu.staging.fused import _GenericBatchStream

    path = _write_libfm(str(tmp_path / "r.libfm"), rows=50)
    s = ell_batches(path + "?format=libfm", _spec())
    assert isinstance(s, FusedEllLibFMBatches)
    total = sum(int(b.n_valid) for b in s)
    s.close()
    assert total == 50
    # non-fusable spec falls back to the generic path, same totals
    g = ell_batches(
        path + "?format=libfm",
        BatchSpec(batch_size=64, layout="ell", max_nnz=4,
                  index_dtype=np.dtype(np.int64)),
    )
    assert isinstance(g, _GenericBatchStream)
    assert sum(int(b.n_valid) for b in g) == 50
    g.close()


@fused
def test_threaded_fan_out_covers(tmp_path):
    path = _write_libfm(str(tmp_path / "t.libfm"), rows=300)
    s = ell_batches(path + "?format=libfm", _spec(B=32), nthread=2)
    labels = [x for b in s for x in b.labels[: b.n_valid].tolist()]
    s.close()
    assert len(labels) == 300


@fused
def test_dispatcher_indexing_mode_kwarg(tmp_path):
    """ell_batches(indexing_mode=1) matches the URI sugar on both the
    fused path and the generic fallback (dense_batches API symmetry)."""
    path = _write_libfm(str(tmp_path / "k.libfm"), rows=60, one_based=True)

    def indices(**kw):
        s = ell_batches(path + "?format=libfm", _spec(), **kw)
        out = [b.indices.copy() for b in s]
        s.close()
        return np.concatenate(out)

    via_kwarg = indices(indexing_mode=1)
    s2 = ell_batches(path + "?format=libfm&indexing_mode=1", _spec())
    via_uri = np.concatenate([b.indices.copy() for b in s2])
    s2.close()
    np.testing.assert_array_equal(via_kwarg, via_uri)


def test_auto_probe_negative_ids_resolve_zero_based(tmp_path):
    """Negative ids in the head must resolve auto mode to 0-based (the
    native CSR rule is min of BOTH fields and features > 0), not shift
    every column by one."""
    from dmlc_core_tpu.staging.fused import _probe_libfm_base

    assert _probe_libfm_base(b"1 2:-3:1.0 4:7:2.0\n") == 0
    assert _probe_libfm_base(b"1 2:3:1.0 -4:7:2.0\n") == 0
    assert _probe_libfm_base(b"1 2:3:1.0 4:7:2.0\n") == 1
    assert _probe_libfm_base(b"1 0:3:1.0\n") == 0


@fused
def test_fuzz_parity(tmp_path):
    """Randomized noisy libfm text stages identically through the fused
    kernel and the generic path (the ELL analogue of
    tests/test_native.py::test_fuzz_parity; runs under ASan via make
    check — TSan is not relevant here: each fused producer owns its
    buffers, threads never share a ring slot)."""
    rng = np.random.default_rng(23)
    junk_pool = ["x", "a:b", "1:2:3:4", ":", "::", "-:-", "7:", ":9",
                 "1:2:nan", "1e3:4", "  "]
    for trial in range(12):
        lines = []
        for _ in range(60):
            toks = []
            r = rng.random()
            if r < 0.15:
                toks.append("junklabel")  # line dropped by both paths
            elif r < 0.4:
                toks.append(f"{rng.normal():.4g}:{abs(rng.normal()):.3g}")
            else:
                toks.append(f"{rng.normal():.4g}")
            for _ in range(int(rng.integers(0, 9))):
                if rng.random() < 0.25:
                    toks.append(str(rng.choice(junk_pool)))
                else:
                    fid = int(rng.integers(-2, 15))
                    feat = int(rng.integers(-2, 3000))
                    if rng.random() < 0.5:
                        toks.append(f"{fid}:{feat}:{rng.normal():.5g}")
                    else:
                        toks.append(f"{fid}:{feat}")
            lines.append(" ".join(toks))
        eol = "\r\n" if trial % 3 == 0 else "\n"
        path = str(tmp_path / f"fz{trial}.libfm")
        with open(path, "w", newline="") as f:
            f.write(eol.join(lines) + eol)
        for dtype in ("float32", "float16"):
            f_b, f_t = _fused(path, _spec(dtype, B=37, K=4))
            g_b, g_t = _generic(path, _spec(dtype, B=37, K=4))
            _assert_equal(f_b, g_b)
            assert f_t == g_t, (trial, dtype)


def test_generic_fallback_without_native(tmp_path, monkeypatch):
    """ell_batches format=libfm works (same totals) when the kernel is
    reported missing."""
    path = _write_libfm(str(tmp_path / "f.libfm"), rows=80)
    monkeypatch.setattr(native, "HAS_LIBFM_ELL", False)
    s = ell_batches(path + "?format=libfm", _spec())
    assert sum(int(b.n_valid) for b in s) == 80
    s.close()


# -- field ids (BatchSpec.fields) ---------------------------------------------


def _field_spec(value_dtype="float32", B=64, K=4, fields=True):
    return BatchSpec(
        batch_size=B, layout="ell", max_nnz=K,
        value_dtype=np.dtype(value_dtype), fields=fields,
    )


def _fused_copies(path, spec, part_index=0, num_parts=1, indexing_mode=0):
    from dmlc_core_tpu.staging import FusedEllLibFMBatches

    stream = FusedEllLibFMBatches(
        path, spec, part_index, num_parts, indexing_mode=indexing_mode
    )
    out = [{k: v.copy() for k, v in b.as_dict().items()}
           | {"packed": b.packed.copy()} for b in stream]
    tr = stream.truncated_nnz
    stream.close()
    return out, tr


def _plain_fields(path, B, K, one_based):
    """Field ids as the pure-Python parser (data/libfm_parser.py) reads
    them, placed at their tokens' positions in [B, K] batches: the
    first K tokens of a row kept, a field outside int32 (a 1-based
    field 0) dropping its slot, padding slots field 0."""
    from dmlc_core_tpu.data.libfm_parser import LibFMParser

    parser = LibFMParser.__new__(LibFMParser)
    parser.param = type("P", (), {"indexing_mode": 1 if one_based else 0})()
    parser.index_dtype = np.int64
    blk = parser._parse_block_py(open(path, "rb").read())
    rows = []
    for r in range(blk.size):
        lo, hi = blk.offset[r], blk.offset[r + 1]
        row = np.zeros(K, np.int32)
        for p, (f, j) in enumerate(zip(blk.field[lo:hi], blk.index[lo:hi])):
            if p < K and 0 <= f < 2**31 and 0 <= j < 2**31:
                row[p] = f
        rows.append(row)
    out = []
    for b0 in range(0, len(rows), B):
        batch = np.zeros((B, K), np.int32)
        chunk = rows[b0 : b0 + B]
        batch[: len(chunk)] = chunk
        out.append(batch)
    return out


@fused
@pytest.mark.parametrize("one_based", [False, True], ids=["base0", "base1"])
@pytest.mark.parametrize("value_dtype", ["float32", "float16"])
def test_fused_fields_match_the_plain_parser(tmp_path, value_dtype, one_based):
    """Fields bit-identical to data/libfm_parser.py at both indexing
    bases, with rows past K truncated and a padded tail batch (500 rows,
    B = 64), junk tokens and a 1-based field 0 in the mix; the generic
    batcher stages the same bytes."""
    path = _write_libfm(str(tmp_path / "f.libfm"), rows=500, k_max=7,
                        one_based=one_based, junk=True)
    with open(path, "a") as f:
        f.write("1 0:5:1.0 3:7:2.0\n")  # field 0: out of range when 1-based
    mode = 1 if one_based else 0
    f, ft = _fused_copies(path, _field_spec(value_dtype), indexing_mode=mode)
    want = _plain_fields(path, 64, 4, one_based)
    assert len(f) == len(want) == 8
    for b, w in zip(f, want):
        assert b["fields"].dtype == np.int32
        np.testing.assert_array_equal(b["fields"], w)
    assert f[-1]["fields"][f[-1]["weights"] == 0].max() == 0
    g, gt = _generic(path, _field_spec(value_dtype), indexing_mode=mode)
    assert ft == gt and ft > 0
    for b, gb in zip(f, g):
        for k, v in gb.as_dict().items():
            assert v.tobytes() == b[k].tobytes(), k


@fused
def test_fused_fields_cover_several_parts(tmp_path):
    path = _write_libfm(str(tmp_path / "p.libfm"), rows=400, k_max=7)
    for part in range(3):
        f, _ = _fused_copies(path, _field_spec(B=32), part, 3)
        g, _ = _generic(path, _field_spec(B=32), part, 3)
        assert len(f) == len(g) > 0
        for b, gb in zip(f, g):
            np.testing.assert_array_equal(b["fields"], gb.fields)
            np.testing.assert_array_equal(b["indices"], gb.indices)


@fused
@pytest.mark.parametrize("value_dtype", ["float32", "float16"])
def test_fields_off_leaves_the_staged_bytes_unchanged(tmp_path, value_dtype):
    """The fields section is appended after every other one: with it
    off, each packed slot is byte for byte the prefix of the slot with
    it on, and every other array is the same."""
    path = _write_libfm(str(tmp_path / "u.libfm"), rows=300, k_max=7,
                        junk=True)
    off, t_off = _fused_copies(path, _field_spec(value_dtype, fields=False))
    on, t_on = _fused_copies(path, _field_spec(value_dtype))
    assert t_off == t_on
    for a, b in zip(off, on):
        assert "fields" not in a
        assert b["packed"][: a["packed"].nbytes].tobytes() == a["packed"].tobytes()
        for k in set(a) - {"packed"}:
            assert a[k].tobytes() == b[k].tobytes(), k


def test_fields_need_libfm_input(tmp_path):
    from dmlc_core_tpu.utils.logging import Error

    with pytest.raises(Error, match="field"):
        BatchSpec(batch_size=8, layout="dense", num_features=4, fields=True)
    path = tmp_path / "d.libsvm"
    path.write_text("1 1:2 3:4\n")
    with pytest.raises(Error, match="field"):
        s = ell_batches(str(path) + "?format=libsvm", _field_spec(B=8))
        list(s)
