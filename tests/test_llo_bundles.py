"""tools/llo_bundles.py on a ten-bundle dump: loop bodies by depth, the
parts of a body between predicated regions, the operations counted by
mnemonic (a store's `vst_source` note is no second store), and an empty
delay slot lying where the bundle before it lies."""

from tools import llo_bundles

DUMP = """\
LB: loop body
PF: predicated region fallthrough
     0   :  { %s1_s0 = smov 0 }
   0x1 LB: > { %p2_p0 = scmp.ge.s32.totalorder %s1_s0, 4 }
   0x2   : > { %3 = sbr.rel (%p2_p0) target bundleno = 9 (0x9) }
   0x3   :  {}
   0x4 LB: >> { %v4_v0 = vld [vmem:[#allocation2 + $0x8] sm:$0xff]  ;;  %v5_v1 = vld [vmem:[#allocation7_spill] sm:$0xff] }
   0x5   : >> { %6 = vmatmul.bf16.gmra.mxu0 %v4_v0  ;;  %7 = vst [vmem:[#allocation7_spill] sm:$0xff] /*vst_source=*/%v5_v1 }
   0x6 PF: > { %v8_v2 = vmax.xlane.f32.xlu0 %v4_v0  ;;  %v9_v3 = vpop.xlane.xlu0 %8 }
   0x7   : > { %v10_v4 = vperm.slane %v9_v3, 0  ;;  %11 = vst.msk [vmem:[#allocation3] sm:$0xff] %vm1, %v10_v4 }
   0x8   : > { %s12_s0 = sadd.s32 1, %s1_s0 }
   0x9   :  { %13 = vst [vmem:[#allocation4] sm:$0xff] %v10_v4 }
"""


def test_loop_bodies_parts_and_counts():
    rows = llo_bundles.bundles(DUMP.splitlines())
    assert len(rows) == 10
    assert [r["depth"] for r in rows] == [0, 1, 1, 1, 2, 2, 1, 1, 1, 0]
    outer, inner = llo_bundles.loop_bodies(rows)
    assert (outer["depth"], outer["addr"], outer["bundles"]) == (1, "0x1", 8)
    assert (inner["depth"], inner["addr"], inner["bundles"]) == (2, "0x4", 2)
    assert inner["ops"] == {"vmatmul": 1, "vst": 1, "vld": 2, "_spill]": 2,
                            ".xlane": 0, "vperm": 0}
    # the outer body in two parts: up to the region's end (the inner
    # loop with it), and from the bundle the branch around it lands on
    assert [(p["addr"], p["bundles"]) for p in outer["parts"]] == [
        ("0x1", 5), ("0x6", 3)]
    assert outer["parts"][1]["ops"] == {
        "vmatmul": 0, "vst": 1, "vld": 0, "_spill]": 0, ".xlane": 2,
        "vperm": 1}
    assert outer["ops"]["vst"] == 2      # the last bundle is no loop's


def test_table_has_a_line_a_body_and_a_line_a_part(tmp_path, capsys):
    path = tmp_path / "k-71-final_bundles.txt"
    path.write_text(DUMP)
    assert llo_bundles.main(["llo_bundles", str(path)]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].split() == ["depth", "at", "bundles", *llo_bundles.OPS]
    assert [line.split()[:3] for line in out[1:]] == [
        [">", "0x1", "8"], ["part", "0x1", "5"], ["part", "0x6", "3"],
        [">>", "0x4", "2"]]
    assert llo_bundles.main(["llo_bundles"]) == 2
