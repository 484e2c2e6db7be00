"""A serve configuration is its own files: the architecture module and the
reference it names are the ones the harness uses, the weights the dense
module lists are those recorded before the module existed, and the counts
sum what each attention layer reads."""
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from bench_helpers import CPU, PEAKS, ROOT, spec_of

from bench import counts, weights
from bench import run as harness
from bench.arch import named
from bench.drivers import serve

DENSE = os.path.join(ROOT, "bench", "arch", "dense.py")
with open(os.path.join(ROOT, "bench", "configs", "internlm2-1.8b.json")) as f:
    FULL = json.load(f)

# internlm2-1.8b at its test size, as bench/weights.py made it before the
# leaf table moved into bench/arch/dense.py: each leaf's padded shape, the
# float64 sum of its values and the head of the sha256 of its bytes
FINGERPRINT = {
    5: {
        'blocks.pos0.attn.wk': ((2, 128, 64), -8.534680277109146,
            '19a2f0a6835495e9'),
        'blocks.pos0.attn.wo': ((2, 128, 128), -19.740814238786697,
            '163b7f6728702fab'),
        'blocks.pos0.attn.wq': ((2, 128, 128), 11.670756220817566,
            '861f41ae0576f289'),
        'blocks.pos0.attn.wv': ((2, 128, 64), -19.475394427776337,
            '856a2c553e2cb7c1'),
        'blocks.pos0.ln1': ((2, 128), 256.0, '8e6b548203bfc086'),
        'blocks.pos0.ln2': ((2, 128), 256.0, '8e6b548203bfc086'),
        'blocks.pos0.mlp.w_down': ((2, 256, 128), -19.584751706570387,
            '7aedadaeb56dc1b3'),
        'blocks.pos0.mlp.w_gate': ((2, 128, 256), 3.641121953725815,
            '8eaeea8b5fdbaa78'),
        'blocks.pos0.mlp.w_up': ((2, 128, 256), -23.765462890267372,
            'bfcd7d86a3ebe996'),
        'embed': ((512, 128), -46.42552208900452, 'aa365814b8d87ce4'),
        'final_norm': ((128,), 128.0, '1ede9ebfa1ad011b'),
        'lm_head': ((128, 512), 17.073290050029755, '9217c68b69008612'),
    },
    2147483671: {
        'blocks.pos0.attn.wk': ((2, 128, 64), -6.761173188686371,
            '61926addc354b4eb'),
        'blocks.pos0.attn.wo': ((2, 128, 128), -9.567505240440369,
            'da9b5164fda86b8f'),
        'blocks.pos0.attn.wq': ((2, 128, 128), 9.529567885678262,
            '5fe758e58df841c3'),
        'blocks.pos0.attn.wv': ((2, 128, 64), 17.172410368919373,
            '146106d193f4847a'),
        'blocks.pos0.ln1': ((2, 128), 256.0, '8e6b548203bfc086'),
        'blocks.pos0.ln2': ((2, 128), 256.0, '8e6b548203bfc086'),
        'blocks.pos0.mlp.w_down': ((2, 256, 128), -5.493684366345406,
            '2374901176e2e40e'),
        'blocks.pos0.mlp.w_gate': ((2, 128, 256), 30.675112336874008,
            'e839b25ed6c5462c'),
        'blocks.pos0.mlp.w_up': ((2, 128, 256), 6.456732273101807,
            '1b59564e6c005709'),
        'embed': ((512, 128), 81.0673041343689, '0cf87bdc611f58be'),
        'final_norm': ((128,), 128.0, '1ede9ebfa1ad011b'),
        'lm_head': ((128, 512), 6.679866127669811, '03ff56f098269175'),
    },
}


@pytest.mark.parametrize("seed", sorted(FINGERPRINT))
def test_dense_weights_match_the_recorded_fingerprint(tiny_serve, seed):
    config, traffic = tiny_serve
    params, _ = serve.build(config, traffic, seed)
    got = {}
    for path, leaf in weights._flatten(params).items():
        a = np.asarray(leaf)
        got[path] = (a.shape, float(a.astype(np.float64).sum()),
                     hashlib.sha256(a.tobytes()).hexdigest()[:16])
    assert got == FINGERPRINT[seed]


def test_dense_leaves_and_reads():
    leaves = named(FULL, "arch").leaves(FULL)
    assert leaves["final_norm"] == ((2048,), "ones")
    assert leaves["blocks.pos0.ln1"] == ((24, 2048), "ones")
    assert leaves["embed"] == ((92544, 2048), ("normal", 1))
    assert leaves["blocks.pos0.attn.wo"] == ((24, 2048, 2048),
                                             ("normal", 2048))
    assert leaves["blocks.pos0.mlp.w_down"] == ((24, 8192, 2048),
                                                ("normal", 8192))
    # every layer reads the whole context with all its heads
    assert named(FULL, "arch").attention_reads(FULL, 1000) == \
        [(1000, 16, 8, 128)] * 24


# appended to a copy of a module: each named function records its calls
MARKER = '''

USED = []


def _marked(name, fn):
    def call(*args, **kw):
        USED.append(name)
        return fn(*args, **kw)
    return call


for _name in {names!r}:
    globals()[_name] = _marked(_name, globals()[_name])
'''


def _marked_copy(src, dst, names):
    shutil.copy(src, dst)
    with open(dst, "a") as f:
        f.write(MARKER.format(names=names))
    return str(dst)


def test_a_configuration_file_names_its_own_modules(tiny_serve, tmp_path):
    """A configuration written outside the tree, naming marked copies of
    the dense module and of the reference, runs through the serve driver
    at test size on those copies, and is correct."""
    config, traffic = tiny_serve
    config["arch"] = _marked_copy(
        DENSE, tmp_path / "arch_copy.py", ["program_config", "leaves"])
    config["reference"] = _marked_copy(
        os.path.join(ROOT, "bench", "reference", "internlm2.py"),
        tmp_path / "reference_copy.py", ["logits"])
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    config = json.loads(path.read_text())
    spec = spec_of(config, traffic, ["tokens_per_s", "itl_p99_ms"])
    res = harness.execute(spec, serve, harness.Context(2**31 + 41, 1.0,
                                                       False, ""),
                          CPU, PEAKS, interpret=True)
    assert res["correct"], res["checks"]
    assert set(named(config, "arch").USED) == {"program_config", "leaves"}
    assert set(named(config, "reference").USED) == {"logits"}


WINDOWED = '''
def matmul_params(config):
    return 100


def attention_reads(config, n):
    """A window of 4 keys in the first layer, the whole context in the
    second, which has half the query heads."""
    return [(min(n, 4), 4, 2, 2), (n, 2, 2, 2)]
'''


def test_counts_sum_what_each_layer_reads(tmp_path):
    (tmp_path / "windowed.py").write_text(WINDOWED)
    cfg = {"arch": str(tmp_path / "windowed.py")}
    # a token at context 10: 4 keys of 4 heads, 10 keys of 2 heads, each
    # key 2 FLOPs in QK and 2 in PV per head dimension (2)
    assert counts.attention_flops(cfg, 10) == 4 * 2 * (4 * 4 + 2 * 10)
    assert counts.token_flops(cfg, 10) == 200 + 288
    # six prompt tokens: the window reads 1, 2, 3, 4, 4, 4 keys
    assert counts.prefill_flops(cfg, 6) == \
        2 * 100 * 6 + 4 * 2 * (4 * 18 + 2 * 21)
    flops, nbytes = counts.paged_attention_work(cfg, [10])
    assert flops == 288
    # K and V of the keys read (2 KV heads x 2 dims x 2 bytes each), the
    # query and the output of each layer's heads
    assert nbytes == (2 * 4 * 2 * 2 * 2 + 2 * 4 * 2 * 2) + \
        (2 * 10 * 2 * 2 * 2 + 2 * 2 * 2 * 2)
