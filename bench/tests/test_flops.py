"""Operations and bytes from shapes, against values worked out by hand for
qwen3-0.6b (hidden 1024, 16 query heads and 8 KV heads of 128, MLP 3072,
28 layers, vocabulary 151936)."""
import json
from pathlib import Path

import flops

CFG = json.loads((Path(flops.__file__).parent / 'configs'
                  / 'qwen3-0.6b_on.internlm2-1.8b_off.json').read_text())
QWEN = CFG['online']['config']

# q 2048 and k, v 1024 each out of 1024; o 2048 → 1024; gate, up, down
LAYER = 1024 * (2048 + 2 * 1024) + 2048 * 1024 + 3 * 1024 * 3072
BODY = 28 * LAYER
UNEMBED = 2 * 1024 * 151936
PER_KEY = 4 * 16 * 128 * 28     # QK^T and PV, every layer


def test_parameter_counts():
    assert flops.layer_params(QWEN) == LAYER == 15_728_640
    assert flops.body_params(QWEN) == BODY == 440_401_920
    assert flops.unembed_flops(QWEN) == UNEMBED == 311_164_928
    assert flops.attn_flops_per_key(QWEN) == PER_KEY == 229_376


def test_decode_step():
    live = [100, 200]
    want = 2 * (2 * BODY + UNEMBED) + PER_KEY * 300
    assert flops.decode_step_flops(QWEN, live) == want == 2_452_750_336


def test_mixed_step_counts_causal_keys():
    # a first chunk of 128 from 0, a second of 72 after 128, one decode row
    # attending to 50 tokens
    keys = (128 * 129 // 2) + (72 * 128 + 72 * 73 // 2) + 50
    assert keys == 20_150
    want = 2 * BODY * 201 + UNEMBED * 3 + PER_KEY * keys
    assert flops.mixed_step_flops(QWEN, [(0, 128), (128, 72)], [50]) == want


def test_paged_decode_call_reads_live_tokens_only():
    f, b = flops.paged_decode_call(QWEN, [100, 200])
    assert f == 4 * 16 * 128 * 300 == 2_457_600
    q_and_out = 2 * 2 * 16 * 128 * 2
    kv = 2 * 300 * 8 * 128 * 2
    assert b == q_and_out + kv == 1_245_184
    # bound by memory: bytes take longer than operations at v5e's peaks
    assert b / 819e9 > f / 197e12
