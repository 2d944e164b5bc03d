"""`tools/same_hlo.py`'s verdict on small hand-written modules: what it sets
aside (where an instruction was traced, the compiler's numbering) and what
it must not (an operand's place, a scope's name, an operation)."""

import sys

import pytest

from tools import same_hlo

MODULE = '''HloModule jit_train_step

FileNames
1 "{root}/ray_tpu/models/{file}.py"

FunctionNames
1 "{function}"

FileLocations
1 {{file_name_id=1 function_name_id=1 line={line} end_line={line} column=4 end_column=9}}

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}

%region_{first}.70 (reduce_sum.288: f32[], reduce_sum.289: f32[]) -> f32[] {{
  %reduce_sum.288 = f32[] parameter(0), metadata={{op_name="reduce_sum"}}
  %reduce_sum.289 = f32[] parameter(1), metadata={{op_name="reduce_sum"}}
  ROOT %reduce_sum.290 = f32[] add(%reduce_sum.288, %reduce_sum.289), metadata={{op_name="jit(train_step)/jvp(norm)/reduce_sum" stack_frame_id={frame}}}
}}

%region_{second}.71 (reduce_sum.295: s32[], reduce_sum.296: s32[]) -> s32[] {{
  %reduce_sum.295 = s32[] parameter(0), metadata={{op_name="reduce_sum"}}
  %reduce_sum.296 = s32[] parameter(1), metadata={{op_name="reduce_sum"}}
  ROOT %reduce_sum.297 = s32[] add(%reduce_sum.295, %reduce_sum.296), metadata={{op_name="jit(train_step)/jvp()/reduce_sum"}}
}}

ENTRY %main.9 (x.1: f32[8,4], n.2: s32[8,4]) -> (f32[8], s32[8]) {{
  %x.1 = f32[8,4] parameter(0)
  %n.2 = s32[8,4] parameter(1)
  %square.12 = f32[8,4] multiply(%x.1, %x.1), metadata={{op_name="jit(train_step)/jvp({scope})/square" stack_frame_id={frame}}}
  %zero.3 = f32[] constant(0)
  %sum.72 = f32[8] reduce({operands}), dimensions={{1}}, to_apply=%region_{first}.70
  %izero.4 = s32[] constant(0)
  %rows.73 = s32[8] {rows}(%n.2, %izero.4), dimensions={{1}}, to_apply=%region_{second}.71
  ROOT %out.5 = (f32[8], s32[8]) tuple(%sum.72, %rows.73)
}}
'''
PARENT = dict(root="/p", file="deepseek_v3", function="_trunk", line=261,
              frame=7, first=56, second=57, scope="norm",
              operands="%square.12, %zero.3", rows="reduce")
CASES = {
    # (what the change's module has otherwise, exit code)
    "the_same_text": ({}, 0),
    "moved_to_another_function_and_file": (
        dict(root="/c", file="layers", function="trunk", line=402, frame=3),
        0),
    "two_parts_traced_the_other_way_round": (dict(first=57, second=56), 0),
    "operands_swapped": (dict(operands="%zero.3, %square.12"), 1),
    "a_scope_renamed": (dict(scope="head_and_loss"), 1),
    "another_operation": (dict(rows="reduce-window"), 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_verdict(case, tmp_path, monkeypatch, capsys):
    change, code = CASES[case]
    paths = []
    for name, fields in (("parent", PARENT), ("change", {**PARENT, **change})):
        paths.append(tmp_path / f"{name}.hlo")
        paths[-1].write_text(MODULE.format(**fields))
    monkeypatch.setattr(sys, "argv", ["same_hlo.py", *map(str, paths),
                                      "--root", "/p", "/c"])
    assert same_hlo.main() == code
    said = capsys.readouterr().out
    assert ("0 differ as printed" in said) == (
        case in ("the_same_text", "moved_to_another_function_and_file"))
    if case == "two_parts_traced_the_other_way_round":
        assert "0 differ with names by order of appearance" in said
