"""``scripts/op_attribution.py``: the parsers that join a device trace's
instruction names to source lines (the compiled module's metadata and
stack-frame tables). The trace reading itself needs a chip."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HLO = """HloModule jit__cholesky_local, is_scheduled=true

FileNames
1 "/root/repo/dlaf_tpu/obs/telemetry.py"
2 "/root/repo/dlaf_tpu/algorithms/cholesky.py"
3 "/root/repo/dlaf_tpu/tile_ops/ozaki.py"

FunctionNames
1 "call"
2 "_cholesky_local"
3 "syrk_f64"
4 "_mirror"

FileLocations
1 {file_name_id=1 function_name_id=1 line=240 end_line=240 column=15 end_column=34}
2 {file_name_id=2 function_name_id=2 line=325 end_line=325 column=31 end_column=76}
3 {file_name_id=3 function_name_id=3 line=492 end_line=492 column=11 end_column=41}
4 {file_name_id=3 function_name_id=4 line=289 end_line=289 column=11 end_column=42}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}
3 {file_location_id=3 parent_frame_id=3}
4 {file_location_id=4 parent_frame_id=4}

ENTRY %main.1 (a.1: f64[256,256]) -> f64[256,256] {
  %copy.7 = f32[256,256]{0,1:T(8,128)} copy(%get-tuple-element.3), metadata={op_name="jit(_cholesky_local)/add" stack_frame_id=4}, backend_config={"flag_configs":[]}
  %get-tuple-element.3 = f32[256,256]{1,0:T(8,128)} get-tuple-element(%while.1), index=2, metadata={op_name="jit(_cholesky_local)/while" stack_frame_id=3}
  ROOT %copy.9 = f32[256,256]{1,0:T(8,128)} copy(%get-tuple-element.3), backend_config={"flag_configs":[]}
}
"""


@pytest.fixture(scope="module")
def attribution():
    spec = importlib.util.spec_from_file_location(
        "op_attribution", os.path.join(ROOT, "scripts", "op_attribution.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_instruction_metadata(attribution):
    meta = attribution.instruction_metadata(HLO)
    assert meta["copy.7"] == ("jit(_cholesky_local)/add", 4)
    assert meta["get-tuple-element.3"] == ("jit(_cholesky_local)/while", 3)
    assert "copy.9" not in meta         # the compiler's own copy: no metadata


@pytest.mark.parametrize("frame,chain", [
    (4, "ozaki.py:289(_mirror) < ozaki.py:492(syrk_f64) "
        "< cholesky.py:325(_cholesky_local)"),
    (2, "cholesky.py:325(_cholesky_local)"),
    (0, ""),
    (99, ""),
])
def test_frame_chain_stops_at_the_program(attribution, frame, chain):
    tables = attribution.frame_tables(HLO)
    assert {k: len(v) for k, v in tables.items()} == {
        "FileNames": 3, "FunctionNames": 4, "FileLocations": 4,
        "StackFrames": 4}
    assert attribution.frame_chain(tables, frame) == chain


def test_frame_chain_stops_at_the_named_program(attribution):
    """``--program``: the scan builder's chains stop at its own name (the
    default stop is the unrolled builder's); without a name a chain stops
    at the first frame in the entry's file, and with neither it runs on."""
    hlo = HLO.replace('2 "_cholesky_local"', '2 "_cholesky_local_scan"')
    tables = attribution.frame_tables(hlo)
    want = ("ozaki.py:289(_mirror) < ozaki.py:492(syrk_f64) "
            "< cholesky.py:325(_cholesky_local_scan)")
    assert attribution.frame_chain(tables, 4, "_cholesky_local_scan") == want
    assert attribution.frame_chain(tables, 4, None, "cholesky.py") == want
    # without either the chain runs on to the outermost frame
    assert attribution.frame_chain(tables, 4).endswith(
        "< telemetry.py:240(call)")
    assert attribution.frame_chain(tables, 4, None, None).endswith(
        "< telemetry.py:240(call)")
    assert not hasattr(attribution, "SITE_PROGRAMS")


ENTRY_HLO = HLO.replace(
    '4 "_mirror"', '4 "_mirror"\n5 "cholesky"').replace(
    "4 {file_name_id=3 function_name_id=4 line=289 end_line=289 column=11 "
    "end_column=42}",
    "4 {file_name_id=3 function_name_id=4 line=289 end_line=289 column=11 "
    "end_column=42}\n5 {file_name_id=2 function_name_id=5 line=2035 "
    "end_line=2035 column=22 end_column=60}").replace(
    "1 {file_location_id=1 parent_frame_id=1}",
    "1 {file_location_id=1 parent_frame_id=6}").replace(
    "4 {file_location_id=4 parent_frame_id=4}",
    "4 {file_location_id=4 parent_frame_id=4}\n"
    "5 {file_location_id=5 parent_frame_id=1}")


def test_the_builders_file_is_the_file_of_the_entry_that_dispatched(
        attribution):
    """No table of sites: the caller of ``telemetry.call`` in the frame
    tables is the entry, its file the builder's, and ``by_site`` is the
    innermost frame in that file."""
    tables = attribution.frame_tables(ENTRY_HLO)
    assert list(attribution.frames(tables, 1)) == [
        ("telemetry.py", 240, "call"), ("cholesky.py", 2035, "cholesky")]
    assert attribution.entry_file(tables) == "cholesky.py"
    assert attribution.entry_file(attribution.frame_tables(HLO)) is None
    assert attribution.builder_site(tables, 4, None, "cholesky.py") \
        == "cholesky.py:325"
    assert attribution.builder_site(tables, 4, "syrk_f64") == "ozaki.py:289"
    assert attribution.builder_site(tables, 4, None, None) == ""
    # every entry that dispatches a program does so from its builder's file
    import importlib

    for module, site in (
            ("dlaf_tpu.algorithms.cholesky", "cholesky.local_scan"),
            ("dlaf_tpu.algorithms.cholesky", "cholesky.dist"),
            ("dlaf_tpu.algorithms.triangular", "triangular_solve.dist"),
            ("dlaf_tpu.eigensolver.reduction_to_band",
             "reduction_to_band.local_scan")):
        with open(importlib.import_module(module).__file__) as f:
            assert f'"{site}"' in f.read()


def test_module_of(attribution):
    modules = [(0, 10, "jit_a"), (20, 30, "jit_b")]
    assert [attribution.module_of(modules, t) for t in (0, 9, 10, 25, 40)] \
        == ["jit_a", "jit_a", "?", "jit_b", "?"]


@pytest.mark.parametrize("reduced, planes", [
    # a four-chip cell: the readers' least busy device, not the sum of four
    ({"worst_device": "/device:TPU:2"}, ["/device:TPU:2"]),
    # one chip: its one plane
    ({"worst_device": "/device:TPU:0"}, ["/device:TPU:0"]),
    # no reduced trace, or a plane it does not know: everything, as before
    (None, ["/device:TPU:0", "/device:TPU:2"]),
    ({"worst_device": "/device:TPU:9"}, ["/device:TPU:0", "/device:TPU:2"]),
])
def test_least_busy_device_of_a_four_chip_workload(attribution, reduced,
                                                   planes):
    devices = {"/device:TPU:0": [(0, 5, "a")], "/device:TPU:2": [(1, 2, "b")]}
    got = attribution.least_busy(devices, reduced)
    assert sorted(got) == planes
    assert all(got[p] is devices[p] for p in planes)
