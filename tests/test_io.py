"""Tier-2 tests: end-to-end dataset round-trips through the registered
'tfrecord' format — mirroring TFRecordIOSuite.scala plus the coverage gaps
SURVEY.md §4 lists (compression round-trip, multi-file read, inference
skipping empty files)."""

import decimal
import glob
import os

import pytest

import tpu_tfrecord.io as tfio
from tpu_tfrecord import wire
from tpu_tfrecord.options import RecordType, TFRecordOptions
from tpu_tfrecord.registry import lookup_format
from tpu_tfrecord.schema import (
    ArrayType,
    BinaryType,
    DecimalType,
    DoubleType,
    FloatType,
    IntegerType,
    LongType,
    StringType,
    StructField,
    StructType,
)

SCHEMA = StructType(
    [
        StructField("id", IntegerType()),
        StructField("IntegerCol", IntegerType()),
        StructField("LongCol", LongType()),
        StructField("FloatCol", FloatType()),
        StructField("DoubleCol", DoubleType()),
        StructField("DecimalCol", DecimalType()),
        StructField("VectorCol", ArrayType(DoubleType())),
        StructField("StringCol", StringType()),
        StructField("BinaryCol", BinaryType()),
    ]
)

ROWS = [
    [11, 1, 23, 10.0, 14.0, decimal.Decimal("1.0"), [1.0, 2.0], "r1", b"\x01"],
    [21, 2, 24, 12.0, 15.0, decimal.Decimal("2.0"), [2.0, 2.0], "r2", b"\x02"],
    [31, 3, 25, 14.0, 16.0, decimal.Decimal("3.0"), [3.0, 2.0], "r3", b"\x03"],
]


def approx_row(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if isinstance(w, decimal.Decimal):
            assert float(g) == pytest.approx(float(w), abs=1e-6)
        elif isinstance(w, float):
            assert g == pytest.approx(w, abs=1e-6)
        elif isinstance(w, list) and w and isinstance(w[0], float):
            assert g == pytest.approx(w, abs=1e-6)
        else:
            assert g == w


class TestExampleRoundTrip:
    """TFRecordIOSuite.scala:117-138."""

    def test_round_trip_with_user_schema(self, sandbox):
        out = str(sandbox / "example")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        table = tfio.read(out, schema=SCHEMA)
        assert table.schema == SCHEMA
        got = sorted(table.rows, key=lambda r: r[0])
        for g, w in zip(got, ROWS):
            approx_row(g, w)

    def test_round_trip_inferred_schema(self, sandbox):
        out = str(sandbox / "example2")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        table = tfio.read(out)
        # Inferred: Integer->long, Double/Decimal->float, Vector->array<float>
        m = {f.name: f.data_type for f in table.schema}
        assert m["id"] == LongType()
        assert m["DoubleCol"] == FloatType()
        assert m["VectorCol"] == ArrayType(FloatType())
        ids = sorted(table.column("id"))
        assert ids == [11, 21, 31]

    def test_success_marker_written(self, sandbox):
        out = str(sandbox / "marker")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        assert tfio.has_success_marker(out)

    def test_column_pruning(self, sandbox):
        out = str(sandbox / "prune")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        table = tfio.read(out, schema=SCHEMA, columns=["StringCol", "id"])
        assert table.schema.names == ["StringCol", "id"]
        assert sorted(table.rows) == [["r1", 11], ["r2", 21], ["r3", 31]]


class TestPartitionBy:
    """TFRecordIOSuite.scala:140-151 + README partitionBy example."""

    SCHEMA = StructType(
        [StructField("number", LongType()), StructField("word", StringType())]
    )
    ROWS = [[8, "bat"], [8, "abc"], [1, "xyz"], [2, "aaa"]]

    def test_layout_and_round_trip(self, sandbox):
        out = str(sandbox / "pt")
        tfio.write(self.ROWS, self.SCHEMA, out, mode="overwrite", partition_by=["number"])
        names = sorted(os.listdir(out))
        assert names == ["_SUCCESS", "number=1", "number=2", "number=8"]
        # partition column comes back (appended at the end) with long type
        table = tfio.read(out)
        assert table.schema.names == ["word", "number"]
        assert table.schema["number"].data_type == LongType()
        assert sorted(table.to_dicts(), key=lambda d: (d["number"], d["word"])) == [
            {"number": 1, "word": "xyz"},
            {"number": 2, "word": "aaa"},
            {"number": 8, "word": "abc"},
            {"number": 8, "word": "bat"},
        ]

    def test_multi_level_partitions(self, sandbox):
        schema = StructType(
            [
                StructField("date", StringType()),
                StructField("shard", LongType()),
                StructField("v", FloatType()),
            ]
        )
        rows = [["2026-01-01", 0, 1.0], ["2026-01-01", 1, 2.0], ["2026-01-02", 0, 3.0]]
        out = str(sandbox / "multi")
        tfio.write(rows, schema, out, mode="overwrite", partition_by=["date", "shard"])
        assert os.path.isdir(os.path.join(out, "date=2026-01-01", "shard=0"))
        table = tfio.read(out)
        assert table.schema.names == ["v", "date", "shard"]
        assert sorted(table.column("v")) == [1.0, 2.0, 3.0]

    def test_partition_value_escaping(self, sandbox):
        schema = StructType(
            [StructField("k", StringType()), StructField("v", LongType())]
        )
        rows = [["a/b:c", 1], [None, 2]]
        out = str(sandbox / "esc")
        tfio.write(rows, schema, out, mode="overwrite", partition_by=["k"])
        dirs = sorted(d for d in os.listdir(out) if d != "_SUCCESS")
        assert dirs == ["k=__HIVE_DEFAULT_PARTITION__", "k=a%2Fb%3Ac"]
        table = tfio.read(out)
        got = sorted(table.to_dicts(), key=lambda d: d["v"])
        assert got[0] == {"v": 1, "k": "a/b:c"}
        assert got[1] == {"v": 2, "k": None}

    def test_partition_column_not_written_to_records(self, sandbox):
        out = str(sandbox / "strip")
        tfio.write(self.ROWS, self.SCHEMA, out, mode="overwrite", partition_by=["number"])
        f = glob.glob(os.path.join(out, "number=8", "*.tfrecord"))[0]
        from tpu_tfrecord import proto

        recs = [proto.parse_example(r) for r in wire.read_records(f)]
        for r in recs:
            assert set(r.features) == {"word"}

    def test_all_columns_partition_rejected(self, sandbox):
        with pytest.raises(ValueError):
            tfio.write(
                [[1]],
                StructType([StructField("x", LongType())]),
                str(sandbox / "bad"),
                partition_by=["x"],
            )


class TestPartitionTypeInference:
    """Strict numeric classification: values Python's int()/float() accept
    but JVM parsing (the reference's substrate) rejects must stay strings."""

    def test_strict_long_and_double(self):
        from tpu_tfrecord.io.paths import infer_partition_type
        from tpu_tfrecord.schema import DoubleType as D, LongType as L, StringType as S

        assert infer_partition_type(["1", "-2", "+3"]) == L()
        assert infer_partition_type(["1", "2.5"]) == D()
        assert infer_partition_type(["1e3", ".5", "3.", "-1.5E-2"]) == D()
        # Java Long.parseLong does not trim; Double.parseDouble does and
        # accepts exact-case NaN/Infinity
        assert infer_partition_type([" 1", "1 ", " 1.5 "]) == D()
        assert infer_partition_type(["NaN", "Infinity", "-Infinity", "2.5"]) == D()
        for v in ["1_0", "inf", "nan", "infinity", "0x10", "1.0f", "", " "]:
            assert infer_partition_type([v]) == S(), v
        # one string value demotes the whole column
        assert infer_partition_type(["1", "1_0"]) == S()
        # None (HIVE default partition) does not affect classification
        assert infer_partition_type([None, "4"]) == L()


class TestStrictOptions:
    def test_unknown_option_raises_with_did_you_mean(self):
        from tpu_tfrecord.options import TFRecordOptions

        with pytest.raises(ValueError, match="verifyCrc"):
            TFRecordOptions.from_map({"verifyCRC": "true"})
        with pytest.raises(ValueError, match="codec"):
            TFRecordOptions.from_map({"codec_": "gzip"})
        with pytest.raises(ValueError, match="Unknown option"):
            TFRecordOptions.from_map({"utterly_bogus_key": 1})

    def test_unknown_option_raises_through_read_api(self, sandbox):
        schema = StructType([StructField("x", LongType())])
        out = str(sandbox / "strict")
        tfio.write([[1]], schema, out, mode="overwrite")
        with pytest.raises(ValueError, match="recordType"):
            tfio.read(out, recordtype="Example")  # typo'd case


class TestSequenceExampleRoundTrip:
    """TFRecordIOSuite.scala:153-167."""

    def test_round_trip(self, sandbox):
        schema = StructType(
            [
                StructField("id", LongType()),
                StructField("FloatArrayOfArray", ArrayType(ArrayType(FloatType()))),
                StructField("StrArrayOfArray", ArrayType(ArrayType(StringType()))),
            ]
        )
        rows = [
            [1, [[1.0, 2.0], [3.0]], [["a"], ["b", "c"]]],
            [2, [[5.0]], [["z"]]],
        ]
        out = str(sandbox / "seq")
        tfio.write(rows, schema, out, mode="overwrite", recordType="SequenceExample")
        table = tfio.read(out, schema=schema, recordType="SequenceExample")
        assert sorted(table.rows, key=lambda r: r[0]) == rows
        # inferred
        t2 = tfio.read(out, recordType="SequenceExample")
        m = {f.name: f.data_type for f in t2.schema}
        assert m["FloatArrayOfArray"] == ArrayType(ArrayType(FloatType()))


class TestByteArrayRoundTrip:
    """TFRecordIOSuite.scala:169-182."""

    def test_round_trip(self, sandbox):
        schema = StructType([StructField("byteArray", BinaryType())])
        rows = [[b"raw-1"], [b"\x00\xff"], [b""]]
        out = str(sandbox / "bytes")
        tfio.write(rows, schema, out, mode="overwrite", recordType="ByteArray")
        table = tfio.read(out, recordType="ByteArray")
        assert table.schema.names == ["byteArray"]
        assert sorted(table.column("byteArray")) == sorted(r[0] for r in rows)


class TestSaveModes:
    """TFRecordIOSuite.scala:184-237."""

    def test_overwrite_replaces(self, sandbox):
        out = str(sandbox / "ow")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        tfio.write(ROWS[:1], SCHEMA, out, mode="overwrite")
        assert len(tfio.read(out, schema=SCHEMA)) == 1

    def test_append_accumulates(self, sandbox):
        out = str(sandbox / "ap")
        tfio.write(ROWS, SCHEMA, out, mode="append")
        tfio.write(ROWS, SCHEMA, out, mode="append")
        assert len(tfio.read(out, schema=SCHEMA)) == 6

    def test_error_if_exists(self, sandbox):
        out = str(sandbox / "er")
        tfio.write(ROWS, SCHEMA, out)
        with pytest.raises(FileExistsError):
            tfio.write(ROWS, SCHEMA, out)  # default mode = error

    def test_ignore_leaves_files_untouched(self, sandbox):
        out = str(sandbox / "ig")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        files_before = {
            f: os.path.getmtime(os.path.join(out, f)) for f in os.listdir(out)
        }
        tfio.write(ROWS[:1], SCHEMA, out, mode="ignore")
        files_after = {
            f: os.path.getmtime(os.path.join(out, f)) for f in os.listdir(out)
        }
        assert files_before == files_after

    def test_unknown_mode_rejected(self, sandbox):
        with pytest.raises(ValueError):
            tfio.write(ROWS, SCHEMA, str(sandbox / "x"), mode="clobber")


class TestCompression:
    """Coverage gap in the reference: no codec round-trip test (SURVEY §4)."""

    @pytest.mark.parametrize("codec,ext", [("gzip", ".gz"), ("deflate", ".deflate")])
    def test_compressed_round_trip(self, sandbox, codec, ext):
        out = str(sandbox / f"comp-{codec}")
        files = tfio.write(ROWS, SCHEMA, out, mode="overwrite", codec=codec)
        assert all(f.endswith(".tfrecord" + ext) for f in files)
        table = tfio.read(out, schema=SCHEMA)  # codec inferred from extension
        assert len(table) == 3

    def test_hadoop_codec_class_name(self, sandbox):
        out = str(sandbox / "hadoopcodec")
        files = tfio.write(
            ROWS, SCHEMA, out, mode="overwrite",
            codec="org.apache.hadoop.io.compress.GzipCodec",
        )
        assert all(f.endswith(".tfrecord.gz") for f in files)


class TestMultiFileAndInference:
    """Coverage gaps: multi-file read; inference picks first non-empty file."""

    def test_multi_file_read_and_glob(self, sandbox):
        out1, out2 = str(sandbox / "m1"), str(sandbox / "m2")
        tfio.write(ROWS[:2], SCHEMA, out1, mode="overwrite")
        tfio.write(ROWS[2:], SCHEMA, out2, mode="overwrite")
        table = tfio.read([out1, out2], schema=SCHEMA)
        assert len(table) == 3
        table_glob = tfio.read(str(sandbox / "m*"), schema=SCHEMA)
        assert len(table_glob) == 3

    def test_inference_skips_empty_files(self, sandbox):
        out = str(sandbox / "withempty")
        os.makedirs(out)
        # an empty file sorts first
        open(os.path.join(out, "part-00000-aaa.tfrecord"), "wb").close()
        from tpu_tfrecord.serde import TFRecordSerializer, encode_row

        ser = TFRecordSerializer(SCHEMA)
        wire.write_records(
            os.path.join(out, "part-00001-bbb.tfrecord"),
            (encode_row(ser, RecordType.EXAMPLE, r) for r in ROWS),
        )
        table = tfio.read(out)
        assert len(table) == 3
        assert "id" in table.schema

    def test_no_input_files_raises(self, sandbox):
        with pytest.raises(FileNotFoundError):
            tfio.read(str(sandbox / "nope"))

    def test_empty_dir_inference_raises(self, sandbox):
        out = str(sandbox / "empty")
        os.makedirs(out)
        with pytest.raises(ValueError, match="infer schema"):
            tfio.read(out)

    def test_infer_schema_all_files_merges(self, sandbox):
        out = str(sandbox / "merge")
        s1 = StructType([StructField("x", LongType())])
        s2 = StructType([StructField("x", FloatType()), StructField("y", StringType())])
        tfio.write([[1]], s1, out, mode="append")
        tfio.write([[1.5, "a"]], s2, out, mode="append")
        r = tfio.reader(out)
        merged = r.infer_schema_all_files()
        m = {f.name: f.data_type for f in merged}
        assert m["x"] == FloatType()  # long+float -> float
        assert m["y"] == StringType()

    def test_infer_schema_all_files_parallel_equals_serial(self, sandbox):
        """Thread-pooled per-shard seqOp (the within-host analog of the
        reference's executor-parallel aggregate,
        TensorFlowInferSchema.scala:40-43) must produce the identical
        schema: partials merge in shard order, not completion order."""
        out = str(sandbox / "par")
        # heterogeneous shards exercise order-sensitive lattice merges
        shapes = [
            StructType([StructField("x", LongType())]),
            StructType([StructField("x", FloatType()), StructField("y", LongType())]),
            StructType([StructField("y", FloatType()), StructField("z", StringType())]),
            StructType([StructField("x", LongType()), StructField("z", StringType())]),
        ]
        rows = [[[1]], [[1.5, 2]], [[2.5, "s"]], [[7, "t"]]]
        for s, rws in zip(shapes, rows):
            tfio.write(rws, s, out, mode="append")
        r = tfio.reader(out)
        serial = r.infer_schema_all_files()
        for workers in (2, 8):
            assert r.infer_schema_all_files(num_workers=workers) == serial
        # single-process multihost entry: assign_shards keeps every shard,
        # the allgather degrades to identity, result identical (the real
        # >1-process leg runs in tests/test_multihost.py via the worker)
        assert r.infer_schema_multihost(num_workers=2) == serial

    @pytest.mark.perf
    def test_infer_schema_all_files_parallel_speedup(self, sandbox):
        """The pool must really overlap shards (VERDICT r4 item 5). Each
        shard open is made to stall 100 ms (seeded chaos, a sleep — no
        spare core needed): serial pays 8 stalls in a row, 4 workers two.
        A ratio of two timings taken here, so it holds on any box; it
        still catches the pool silently degrading to serial."""
        import time as _time

        from tpu_tfrecord.faults import FaultPlan, FaultRule, install_chaos

        out = str(sandbox / "speed")
        schema = StructType(
            [StructField("a", LongType()), StructField("s", StringType())]
        )
        rows = [[v, "x" * 20] for v in range(200)]
        for _ in range(8):
            tfio.write(rows, schema, out, mode="append")
        r = tfio.reader(out)

        def timed(**kw):
            plan = FaultPlan(
                [FaultRule(op="open", kind="stall", times=None, stall_ms=100)]
            )
            try:
                with install_chaos(plan):
                    t0 = _time.perf_counter()
                    got = r.infer_schema_all_files(**kw)
                    return _time.perf_counter() - t0, got, len(plan.ledger)
            finally:
                plan.release()

        t_serial, serial, opens_serial = timed()
        t_parallel, parallel, opens_parallel = timed(num_workers=4)
        assert parallel == serial
        assert opens_serial == opens_parallel == 8
        assert t_serial >= 8 * 0.1
        assert t_parallel < t_serial / 1.3, (t_serial, t_parallel)


class TestRegistry:
    def test_lookup_format(self):
        ds = lookup_format("tfrecord")
        assert ds.short_name == "tfrecord"
        assert ds == lookup_format("TFRECORD")

    def test_unknown_format(self):
        with pytest.raises(ValueError):
            lookup_format("parquet-nope")


class TestSaveModeExistenceSemantics:
    """Spark parity: an existing-but-empty directory counts as 'exists' for
    error/ignore modes (path existence, not data-file presence)."""

    def test_error_on_empty_existing_dir(self, sandbox):
        out = str(sandbox / "emptydir")
        os.makedirs(out)
        with pytest.raises(FileExistsError):
            tfio.write(ROWS, SCHEMA, out)  # default ErrorIfExists

    def test_ignore_on_empty_existing_dir(self, sandbox):
        out = str(sandbox / "emptydir2")
        os.makedirs(out)
        assert tfio.write(ROWS, SCHEMA, out, mode="ignore") == []
        assert os.listdir(out) == []

    def test_overwrite_and_append_on_empty_dir_proceed(self, sandbox):
        out = str(sandbox / "emptydir3")
        os.makedirs(out)
        assert len(tfio.write(ROWS, SCHEMA, out, mode="overwrite")) > 0
        out2 = str(sandbox / "emptydir4")
        os.makedirs(out2)
        assert len(tfio.write(ROWS, SCHEMA, out2, mode="append")) > 0

    def test_failed_job_does_not_poison_retry(self, sandbox):
        """A failed first write must not leave an empty output dir that
        flips error/ignore semantics on retry (review regression)."""
        out = str(sandbox / "retry")

        def bad_rows():
            yield ROWS[0]
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError):
            tfio.write(bad_rows(), SCHEMA, out)  # default mode=error
        assert not os.path.exists(out)
        # retry with fixed data now succeeds under the same mode
        assert len(tfio.write(ROWS, SCHEMA, out)) > 0

    def test_overwrite_preserves_other_jobs_temp(self, sandbox):
        """Overwrite clears data but must not delete another job's in-flight
        _temporary shards (review regression)."""
        out = str(sandbox / "owtemp")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        other = os.path.join(out, "_temporary", "other-job")
        os.makedirs(other)
        open(os.path.join(other, "inflight.tmp"), "wb").close()
        tfio.write(ROWS[:1], SCHEMA, out, mode="overwrite")
        assert os.path.exists(os.path.join(other, "inflight.tmp"))
        assert len(tfio.read(out, schema=SCHEMA)) == 1  # old data cleared


class TestUncoveredReadPaths:
    def test_inference_on_compressed_dataset(self, sandbox):
        out = str(sandbox / "gzinf")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite", codec="gzip")
        table = tfio.read(out)  # no schema: infer from .gz shards
        assert sorted(table.column("id")) == [11, 21, 31]

    def test_byte_array_with_partitions(self, sandbox):
        schema = StructType(
            [StructField("byteArray", BinaryType()), StructField("day", StringType())]
        )
        rows = [[b"p1", "a"], [b"p2", "b"]]
        out = str(sandbox / "bap")
        tfio.write(rows, schema, out, mode="overwrite", partition_by=["day"],
                   recordType="ByteArray")
        table = tfio.read(out, recordType="ByteArray")
        got = sorted(table.to_dicts(), key=lambda d: d["byteArray"])
        assert got == [{"byteArray": b"p1", "day": "a"}, {"byteArray": b"p2", "day": "b"}]

    def test_unknown_column_select_names_available(self, sandbox):
        out = str(sandbox / "badsel")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        with pytest.raises(ValueError, match="available"):
            tfio.read(out, schema=SCHEMA, columns=["id", "nope"])


class TestReadGuard:
    """read() materializes Python row lists — refuse huge datasets unless
    the caller opts in (VERDICT r2 weak #5)."""

    def test_limit_returns_head_and_closes_files(self, sandbox):
        from tpu_tfrecord.schema import LongType as LT

        schema = StructType([StructField("n", LT())])
        out = str(sandbox / "lim")
        tfio.write([[i] for i in range(50)], schema, out, mode="overwrite")
        table = tfio.read(out, schema=schema, limit=7)
        assert len(table) == 7
        assert tfio.read(out, schema=schema, limit=0).rows == []

    def test_oversized_dataset_refused_with_guidance(self, sandbox):
        out = str(sandbox / "big")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        with pytest.raises(ValueError, match="TFRecordDataset"):
            tfio.read(out, schema=SCHEMA, max_bytes=1)

    def test_limit_or_max_bytes_override_lifts_guard(self, sandbox):
        out = str(sandbox / "big2")
        tfio.write(ROWS, SCHEMA, out, mode="overwrite")
        assert len(tfio.read(out, schema=SCHEMA, max_bytes=1, limit=2)) == 2
        assert len(tfio.read(out, schema=SCHEMA, max_bytes=None)) == len(ROWS)
