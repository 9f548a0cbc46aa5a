"""``stacked_gemm_roofline`` reads the projection GEMM in the form that
takes a prefetched layer index, and nothing else: not the GEMM without
one, which ``gemm_roofline`` reads, nor the paged decode kernel."""

import json
import re
from pathlib import Path

import pytest

from bench import devtrace, harness
from bench.devtrace import DeviceTrace, Event

FIXTURE = Path(__file__).parent / "fixtures" / "trace_qwen_chat.json"
DEV = "/device:TPU:0"
KERNEL = harness.metric_reader("stacked_gemm_roofline").KERNEL

_OS = ('%closed_call.7 = bf16[128,2560]{1,0} custom-call(s32[1]{0} %li.1, '
       'bf16[128,2560]{1,0} %pad.2, bf16[10240,2560]{1,0} %gte.3, '
       'f32[128,2560]{1,0} %pad.4), custom_call_target="tpu_custom_call"')
_WS = ('%closed_call.8 = bf16[128,6912]{1,0} custom-call(s32[1]{0} %li.1, '
       'bf16[10240,6912]{1,0} %gte.5, bf16[128,2560]{1,0} %pad.2, '
       'f32[1,6912]{1,0} %broadcast.6), custom_call_target="tpu_custom_call"')
_PLAIN = ('%closed_call.9 = bf16[128,2560]{1,0} custom-call('
          'bf16[128,2560]{1,0} %pad.2, bf16[2560,2560]{1,0} %slice.3, '
          'f32[128,2560]{1,0} %pad.4), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("line,stacked", [(_OS, True), (_WS, True),
                                          (_PLAIN, False)])
def test_matches_the_stacked_form_alone(line, stacked):
    assert bool(re.search(KERNEL, line)) == stacked
    assert bool(re.search(devtrace.GEMM_OP, line)) != stacked
    assert not re.search(devtrace.PAGED_DECODE_OP, line)


def test_reads_time_of_stacked_calls():
    tr = DeviceTrace({DEV: [Event(_OS, 0, 10), Event(_WS, 10, 25),
                            Event(_PLAIN, 25, 40)]}, {DEV: []}, [])
    assert devtrace.op_time_ns(tr, DEV, KERNEL) == 25


def test_finds_nothing_in_a_gemm_without_a_layer_index():
    """A recorded decode step whose GEMMs take their weight as one matrix:
    the reader's pattern matches none of its ops."""
    tr = DeviceTrace.from_json(json.loads(FIXTURE.read_text()))
    dev = tr.devices()[0]
    assert devtrace.op_time_ns(tr, dev, KERNEL) == 0
    assert devtrace.op_time_ns(tr, dev, devtrace.GEMM_OP) > 0
