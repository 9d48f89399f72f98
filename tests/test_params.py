"""Parameter registry parity tests (reference X-macro registry,
inc/Core/BKT/ParameterDefinitionList.h + BKTIndex.cpp:537-573)."""

import os

import numpy as np
import pytest

import sptag_tpu as sp
from sptag_tpu.core.params import BKTParams, KDTParams
from sptag_tpu.core.types import DistCalcMethod


def test_bkt_defaults_match_reference():
    p = BKTParams()
    assert p.get_param("BKTNumber") == "1"
    assert p.get_param("BKTKmeansK") == "32"
    assert p.get_param("BKTLeafSize") == "8"
    assert p.get_param("Samples") == "1000"
    assert p.get_param("TPTNumber") == "32"
    assert p.get_param("TPTLeafSize") == "2000"
    assert p.get_param("NeighborhoodSize") == "32"
    assert p.get_param("GraphNeighborhoodScale") == "2"
    assert p.get_param("CEF") == "1000"
    assert p.get_param("AddCEF") == "500"
    assert p.get_param("MaxCheckForRefineGraph") == "8192"
    assert p.get_param("DistCalcMethod") == "Cosine"
    assert p.get_param("MaxCheck") == "8192"
    assert p.get_param("NumberOfInitialDynamicPivots") == "50"
    assert p.get_param("NumberOfOtherDynamicPivots") == "4"
    assert p.get_param("DeletePercentageForRefine") == "0.4"
    assert p.get_param("AddCountForRebuild") == "1000"
    assert (p.get_param("ThresholdOfNumberOfContinuousNoBetterPropagation")
            == "3")
    assert p.get_param("TreeFilePath") == "tree.bin"


def test_kdt_defaults_match_reference():
    p = KDTParams()
    assert p.get_param("KDTNumber") == "1"
    assert p.get_param("NumTopDimensionKDTSplit") == "5"
    assert p.get_param("Samples") == "100"
    assert p.get_param("NumTopDimensionTPTSplit") == "5"


def test_set_param_case_insensitive_and_typed():
    p = BKTParams()
    assert p.set_param("maxcheck", "2048")
    assert p.max_check == 2048
    assert p.set_param("DistCalcMethod", "L2")
    assert p.dist_calc_method == DistCalcMethod.L2
    assert p.get_param("DistCalcMethod") == "L2"
    assert not p.set_param("NoSuchParam", "1")
    assert p.get_param("NoSuchParam") is None


def test_save_config_round_trip():
    p = BKTParams()
    p.set_param("MaxCheck", "4096")
    text = p.save_config()
    assert "MaxCheck=4096" in text
    q = BKTParams()
    section = dict(line.split("=", 1) for line in text.strip().splitlines())
    q.load_config(section)
    assert q.max_check == 4096
    assert q.save_config() == text


def test_memory_estimators_reference_formula():
    """Parity with VectorIndex::EstimatedMemoryUsage/EstimatedVectorCount
    (VectorIndex.cpp:403-437): per-row unit = value bytes * dim + 8 (meta
    offset) + 4 * neighborhood (graph) + 1 (tombstone) + tree nodes."""
    import sptag_tpu as sp

    # BKT float, d=128, m=32, 1 tree: 512 + 8 + 128 + 1 + 12 = 661 B/row
    assert sp.estimated_memory_usage(1, 128, "BKT", "Float") == 661
    assert sp.estimated_memory_usage(1000, 128, "BKT", "Float") == 661_000
    # KDT node = 16 B; int8 vector = 128 B
    assert sp.estimated_memory_usage(1, 128, "KDT", "Int8") == \
        128 + 8 + 128 + 1 + 16
    # inverse relation
    n = sp.estimated_vector_count(1 << 30, 128, "BKT", "Float")
    assert n == (1 << 30) // 661
    # hbm estimate is positive and grows with n
    a = sp.estimated_hbm_usage(1000, 128, "Float")
    b = sp.estimated_hbm_usage(2000, 128, "Float")
    assert 0 < a < b


def test_refine_accuracy_floor_parameter():
    """RefineAccuracyFloor (ADVICE r5): the guard's rollback floor is a
    tunable parameter next to RefineAccuracyGuard, not a hardcoded 0.35,
    and it flows from the registry into the RNG graph builder."""
    p = BKTParams()
    assert p.get_param("RefineAccuracyFloor") == "0.35"
    assert p.set_param("RefineAccuracyFloor", "0.2")
    assert p.refine_accuracy_floor == 0.2
    # present in both graph-index registries
    assert KDTParams().get_param("RefineAccuracyFloor") == "0.35"
    # config round trip
    text = p.save_config()
    assert "RefineAccuracyFloor=0.2" in text
    q = BKTParams()
    q.load_config(dict(line.split("=", 1)
                       for line in text.strip().splitlines()))
    assert q.refine_accuracy_floor == 0.2
    # reaches the graph builder (algo/bkt._new_graph -> rng ctor)
    import sptag_tpu as sp

    idx = sp.create_instance("BKT", "Float")
    assert idx.set_parameter("RefineAccuracyFloor", "0.15")
    g = idx._new_graph()
    assert g.refine_accuracy_floor == 0.15
    assert g.refine_accuracy_guard


# ---------------------------------------------------------------------------
# a name that left the registry (RooflineProbe)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["BKT", "KDT", "FLAT"])
def test_folder_saved_with_rooflineprobe_still_loads(algo, tmp_path):
    """Every folder `save_config` wrote before the parameter left carries
    a `RooflineProbe=0` line (the benchmark's cached indexes among them):
    `load_config` skips a name it does not know, the index answers as it
    did, the name is refused like any unknown one and is not written
    again."""
    rng = np.random.default_rng(7)
    data = rng.standard_normal((300, 16)).astype(np.float32)
    index = sp.create_instance(algo, "Float")
    for name, value in (("DistCalcMethod", "L2"), ("TPTNumber", "2"),
                        ("CEF", "32"), ("MaxCheckForRefineGraph", "64"),
                        ("NeighborhoodSize", "8"),
                        ("FinalRefineSearchMode", "same")):
        index.set_parameter(name, value)
    index.build(data)
    assert index.set_parameter("RooflineProbe", "1") is False
    assert index.get_parameter("RooflineProbe") is None
    before = index.search_batch(data[:8], 5)

    folder = str(tmp_path / "saved")
    index.save_index(folder)
    ini = os.path.join(folder, "indexloader.ini")
    with open(ini) as f:
        lines = f.read().splitlines()
    assert not [ln for ln in lines if ln.startswith("RooflineProbe")]
    at = next(i for i, ln in enumerate(lines)
              if ln.startswith("DeviceBytesLedger="))
    lines.insert(at, "RooflineProbe=0")         # where the parent wrote it
    with open(ini, "w") as f:
        f.write("\n".join(lines) + "\n")

    loaded = sp.load_index(folder)
    assert loaded.get_parameter("RooflineProbe") is None
    assert loaded.get_parameter("DeviceBytesLedger") == "1"
    after = loaded.search_batch(data[:8], 5)
    np.testing.assert_array_equal(after[1], before[1])
    np.testing.assert_allclose(after[0], before[0], rtol=1e-6)
