"""What is read from the compiled HLO's text, on a text small enough to
check by eye."""

from benchmark.lib import compile_info as C

HLO = '''
  %fusion.12 = bf16[4,8]{1,0} fusion(%p0, %p1), kind=kOutput, calls=%fc.12, metadata={op_name="jit(_step)/transpose(jvp(block_3))/dot_general" source_file="x.py"}
  %custom-call.5 = (f32[2]) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/pallas_call"}
  ROOT %custom-call.6 = (f32[2]) custom-call(%a), custom_call_target="tpu_custom_call"
  %custom-call.7 = f32[2] custom-call(%a), custom_call_target="Sharding"
  %all-reduce-start.1 = f32[8] all-reduce-start(%g), channel_id=1
  %all-reduce-done.1 = f32[8] all-reduce-done(%all-reduce-start.1)
  %all-gather.3 = f32[32] all-gather(%h), channel_id=2
'''
STABLEHLO = '''
  %1 = "stablehlo.all_reduce"(%0) ...
  %2 = "stablehlo.all_reduce"(%1) ...
  %3 = "stablehlo.reduce_scatter"(%2) ...
'''


def test_pallas_calls_are_the_tpu_custom_calls():
    assert C.count_pallas_calls(HLO) == 2
    assert C.pallas_call_names(HLO) == ["custom-call.5", "custom-call.6"]


def test_collectives_requested_and_compiled():
    counts = C.count_collectives(STABLEHLO, HLO)
    assert counts["requested"]["all-reduce"] == 2
    assert counts["requested"]["reduce-scatter"] == 1
    # an asynchronous pair counts once, at its start
    assert counts["compiled"]["all-reduce"] == 1
    assert counts["compiled"]["all-gather"] == 1
    assert counts["compiled"]["reduce-scatter"] == 0
    assert sorted(C.collective_names(HLO)) == [
        "all-gather.3", "all-reduce-done.1", "all-reduce-start.1"
    ]


def test_labels_are_the_op_names():
    labels = C.instruction_labels(HLO)
    assert labels["fusion.12"].endswith("block_3))/dot_general")
    assert labels["custom-call.5"] == "jit(_step)/pallas_call"
    assert "custom-call.6" not in labels


def test_planned_peak_is_arguments_outputs_less_aliases_plus_temporaries():
    class Mem:
        argument_size_in_bytes = 1500
        output_size_in_bytes = 1490
        alias_size_in_bytes = 1480
        temp_size_in_bytes = 8000

    assert C.planned_bytes(Mem())["peak_bytes"] == 1500 + 1490 - 1480 + 8000
