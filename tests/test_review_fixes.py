"""Regressions for review findings: converter delimiter/raw fields, batch
id aliasing on rewrite, sparse-batch exports, MultiPoint proximity."""

import numpy as np
import pytest

from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features import FeatureBatch
from geomesa_tpu.features.feature_type import parse_spec
from geomesa_tpu.geometry import MultiPoint
from geomesa_tpu.io.converters import converter_from_config
from geomesa_tpu.io.export import to_csv, to_geojson
from geomesa_tpu.process.proximity import proximity_process

MS_2018 = 1514764800000


def _sft_store():
    ds = TpuDataStore()
    ds.create_schema("t", "name:String,dtg:Date,*geom:Point")
    return ds


def test_delimited_custom_delimiter():
    ds = _sft_store()
    conv = converter_from_config(ds.get_schema("t"), {
        "type": "delimited-text",
        "delimiter": "|",
        "fields": [
            {"name": "name", "transform": "$0"},
            {"name": "dtg", "transform": "isoDate('2018-01-01T00:00:00Z')"},
            {"name": "geom", "transform": "point($1, $2)"},
        ],
    })
    batch = conv.convert("a|-75.0|40.0\nb|-74.0|41.0\n")
    assert len(batch) == 2
    assert list(batch.columns["name"]) == ["a", "b"]


def test_json_transformless_field():
    ds = _sft_store()
    conv = converter_from_config(ds.get_schema("t"), {
        "type": "json",
        "fields": [
            {"name": "name"},
            {"name": "dtg", "transform": "isoDate('2018-01-01T00:00:00Z')"},
            {"name": "geom", "transform": "point($lon, $lat)"},
        ],
    })
    batch = conv.convert('{"name": "x", "lon": -75.0, "lat": 40.0}\n')
    assert len(batch) == 1
    assert batch.columns["name"][0] == "x"


def test_rewrite_same_batch_unique_ids():
    ds = _sft_store()
    b = FeatureBatch.from_dict(ds.get_schema("t"), {
        "name": np.array(["a", "b"], dtype=object),
        "dtg": np.array([MS_2018, MS_2018], dtype=np.int64),
        "geom": (np.array([-75.0, -74.0]), np.array([40.0, 41.0])),
    })
    orig_ids = b.ids.copy()
    ds.write("t", b)
    np.testing.assert_array_equal(b.ids, orig_ids)  # caller batch untouched
    ds.write("t", b)
    stored = ds.query("t")
    assert len(stored) == 4
    assert len(set(stored.ids)) == 4


def test_export_sparse_batch():
    ds = _sft_store()
    # write a batch missing the 'name' column entirely
    ds.write("t", {
        "dtg": np.array([MS_2018], dtype=np.int64),
        "geom": (np.array([-75.0]), np.array([40.0])),
    })
    out = ds.query("t")
    # must not have 'name' materialized
    assert "name" not in out.columns
    csv_text = to_csv(out)
    assert "2018-01-01" in csv_text
    gj = to_geojson(out)
    assert '"type": "FeatureCollection"' in gj or "FeatureCollection" in gj


def test_proximity_multipoint():
    ds = _sft_store()
    n = 500
    rng = np.random.default_rng(5)
    ds.write("t", {
        "name": np.array(["p"] * n, dtype=object),
        "dtg": np.full(n, MS_2018, dtype=np.int64),
        "geom": (rng.uniform(-75.5, -74.5, n), rng.uniform(39.5, 40.5, n)),
    })
    mp = MultiPoint(np.array([[-75.0, 40.0], [-74.6, 40.4]]))
    pos = proximity_process(ds, "t", [mp], 20_000.0)
    # oracle: haversine to either point
    x = ds.query("t").columns.get("geom")
    bx, by = ds.query("t").geom_xy()
    from geomesa_tpu.process.knn import haversine_m
    d = np.minimum(haversine_m(-75.0, 40.0, bx, by),
                   haversine_m(-74.6, 40.4, bx, by))
    want = np.sort(np.nonzero(d <= 20_000.0)[0])
    np.testing.assert_array_equal(np.sort(pos), want)


# -- round-2 review fixes ---------------------------------------------------

def test_wkb_decode_ewkb_srid_and_z():
    """PostGIS EWKB (SRID flag + payload) and ISO WKB Z types decode to the
    correct 2-D coordinates instead of reading the SRID as doubles."""
    import struct
    from geomesa_tpu.geometry.wkb import wkb_decode
    ewkb_pt = (bytes([1]) + struct.pack("<I", 0x20000001)
               + struct.pack("<I", 4326) + struct.pack("<dd", 1.0, 2.0))
    g = wkb_decode(ewkb_pt)
    assert (g.x, g.y) == (1.0, 2.0)
    ewkb_ls = (bytes([1]) + struct.pack("<I", 0x20000002)
               + struct.pack("<I", 4326) + struct.pack("<I", 2)
               + struct.pack("<dddd", 0.0, 0.0, 1.0, 1.0))
    g = wkb_decode(ewkb_ls)
    assert g.coords.shape == (2, 2) and g.coords[1, 1] == 1.0
    iso_pz = (bytes([1]) + struct.pack("<I", 1001)
              + struct.pack("<ddd", 3.0, 4.0, 5.0))
    g = wkb_decode(iso_pz)
    assert (g.x, g.y) == (3.0, 4.0)


def test_twkb_precision_out_of_range_rejected():
    from geomesa_tpu.geometry.types import Point
    from geomesa_tpu.geometry.wkb import twkb_decode, twkb_encode
    with pytest.raises(ValueError):
        twkb_encode(Point(1.5, 2.5), precision=8)
    with pytest.raises(ValueError):
        twkb_encode(Point(1.5, 2.5), precision=-9)
    g = twkb_decode(twkb_encode(Point(1.5, 2.5), precision=7))
    assert (g.x, g.y) == (1.5, 2.5)


def test_avro_polygon_and_secondary_geometry_roundtrip():
    import io as _io
    from geomesa_tpu.geometry.types import Polygon
    from geomesa_tpu.io.avro import from_avro, to_avro

    sft = parse_spec("poly", "name:String,*geom:Polygon")
    poly = Polygon(np.array([[0, 0], [1, 0], [1, 1], [0, 0]], dtype=float))
    b = FeatureBatch.from_dict(sft, {"name": ["a"], "geom": [poly]},
                               ids=["f1"])
    buf = _io.BytesIO()
    to_avro(b, buf)
    buf.seek(0)
    rt = from_avro(buf, sft)
    assert len(rt) == 1 and rt.geoms.geometry(0).geom_type == "Polygon"

    sft2 = parse_spec("t2", "name:String,*geom:Point,geom2:Point")
    b2 = FeatureBatch.from_dict(sft2, {
        "name": ["a"],
        "geom": (np.array([1.0]), np.array([2.0])),
        "geom2": (np.array([3.0]), np.array([4.0])),
    }, ids=["f1"])
    buf = _io.BytesIO()
    to_avro(b2, buf)
    buf.seek(0)
    rt2 = from_avro(buf, sft2)
    x2, y2 = rt2.geom_xy("geom2")
    assert (x2[0], y2[0]) == (3.0, 4.0)


def test_profile_context():
    from geomesa_tpu.utils.profiling import Timings, profile
    t = Timings()
    with profile("phase.a", sink=t):
        sum(range(1000))
    with profile("phase.a", sink=t):
        pass
    assert len(t.times["phase.a"]) == 2
    assert t.total_ms("phase.a") >= 0
    assert "phase.a" in repr(t)
