"""The latent-attention decode cell at a tiny size on the CPU: the served
tokens agree with the MLA reference, every slot decodes through the
window, and the cell's per-layer readers find their numbers."""

from bench.tests import tiny, tiny_mla

MIX = {"kind": "serve_closed_decode_mla", "slots": 4, "max_seq": 1024,
       "prefill_chunk": 32, "page_size": 32, "prefill_wave": 2,
       "prompt_min": 64, "prompt_max": 160, "sample_requests": 2,
       "trace_s": 1.0}


def test_tiny_latent_decode_cell_is_correct(tmp_path):
    root = tiny.make_root(tmp_path, [("tiny-mla", "tiny-latent")],
                          extra_configs=[tiny_mla.TINY_MLA],
                          extra_mixes={"tiny-latent": MIX})
    out = tiny.run(root, "tiny-mla.tiny-latent", seconds=2.0, trace=True)
    assert out["checks"]["unserved"]["value"] == 0
    # the tiny cell's limit (0.05) is ten times what sound runs read
    assert out["checks"]["served_gap"]["value"] < 0.05
    assert out["correct"] is True
    m = out["metrics"]
    assert 0 < m["decode_step_mfu.mla"]["value"]
    # tokens of the busiest (layer, held expert) over the mean: >= 100
    assert m["expert_load_peak_pct.serve"]["value"] >= 100.0


def test_mla_yardstick_counts_the_weights_a_decode_step_reads():
    """``flops_mla.weight_bytes`` of the cell's configuration is every
    weight of the seeded tree but the embedding table (gathered, not
    read whole), and a kernel call's bytes are the live latent rows,
    the bf16 query and the float32 output."""
    import math
    import os

    from bench import flops_mla, model_config
    from bench.reference import deepseek_v2, deepseek_v2_weights
    from bench.weights import _is_leaf
    import jax

    conf = model_config.load(os.path.join(
        tiny.REPO, "bench", "configs", "deepseek-v2-lite-ep8.json"))
    s = deepseek_v2.shape_of(conf)
    tree = dict(deepseek_v2_weights.layout(s))
    tree.pop("embed")
    leaves = jax.tree.leaves(tree, is_leaf=_is_leaf)
    want = sum(math.prod(shp) * (4 if kind == "router" else 2)
               for shp, kind in leaves)
    assert flops_mla.weight_bytes(s) == want
    assert abs(want - 5.81e9) < 0.01e9
    _, nbytes = flops_mla.latent_decode_call(s, [100, 1])
    assert nbytes == 101 * 576 * 2 + 2 * 16 * (576 * 2 + 512 * 4)
