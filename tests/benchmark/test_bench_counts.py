"""The benchmark's own operation and byte counts, against values worked
out by hand at mixtral-8x22b and DBRX widths, and its peaks table.  The
counts come from the reference module each configuration file names."""
import pytest

from benchmarks.chip import flops, peaks, reference, spec

BENCH = spec.load_benchmark()


def _dims(name):
    config = spec.config(BENCH, name)
    return spec.reference(config).dims_of(config)


MIXTRAL = _dims("mixtral-8x22b")
DBRX = _dims("dbrx")


def test_configs_keep_the_published_widths():
    assert (MIXTRAL.d_model, MIXTRAL.n_heads, MIXTRAL.n_kv_heads,
            MIXTRAL.head_dim, MIXTRAL.n_experts, MIXTRAL.top_k,
            MIXTRAL.d_ff_expert, MIXTRAL.vocab, MIXTRAL.n_layers) == (
        6144, 48, 8, 128, 8, 2, 16384, 32000, 1)
    assert (DBRX.d_model, DBRX.n_heads, DBRX.n_kv_heads, DBRX.head_dim,
            DBRX.n_experts, DBRX.top_k, DBRX.d_ff_expert, DBRX.vocab,
            DBRX.n_layers) == (6144, 48, 8, 128, 16, 4, 10752, 100352, 1)


def test_departures_give_what_the_program_serves():
    mix = spec.config(BENCH, "mixtral-8x22b")
    dbrx = spec.config(BENCH, "dbrx")
    # the files keep the source's values; the reference reads the served
    assert (mix["rope_theta"], mix["rms_norm_eps"]) == (1e6, 1e-5)
    assert (MIXTRAL.rope_theta, MIXTRAL.rms_norm_eps) == (1e4, 1e-6)
    assert dbrx["attn_config"]["rope_theta"] == 5e5
    assert (DBRX.rope_theta, DBRX.rms_norm_eps) == (1e4, 1e-6)
    # only depth is cut, and one reference module serves both
    reduced = {c["name"]: c["reduced"] for c in BENCH["configs"]}
    assert reduced["mixtral-8x22b"] == ["num_hidden_layers"]
    assert reduced["dbrx"] == ["n_layers"]
    assert mix["reference"] == dbrx["reference"] == "moe_gqa"
    assert reference.served({}, "rope_theta", 3.0) == 3.0


def test_layer_params():
    # attention 6144*128*(2*48 + 2*8) = 88,080,384; router 6144*8;
    # two experts of 3*6144*16384
    assert MIXTRAL.layer_matmul_params() == (
        88_080_384 + 49_152 + 603_979_776)
    # router 6144*16; four experts of 3*6144*10752
    assert DBRX.layer_matmul_params() == (
        88_080_384 + 98_304 + 792_723_456)


def test_decode_token_flops():
    # 2 * (692,109,312 + 6144*32000) + 4 * 1000 * 48 * 128
    assert MIXTRAL.decode_token_flops(1000) == 1_802_010_624
    # 2 * (880,902,144 + 6144*100352) + 4 * 10 * 48 * 128
    assert DBRX.decode_token_flops(10) == 2_995_175_424


def test_prefill_flops():
    # 2*128*692,109,312 + 4*(128*129/2)*48*128 + 2*6144*32000
    assert MIXTRAL.prefill_flops(128) == 177_776_099_328


def test_decode_attention_cost():
    f, b = MIXTRAL.decode_attention_cost([100, 300])
    assert f == 4 * 400 * 48 * 128 == 9_830_400
    # q and out: 2 rows * 48 * 128 * 2 B each; K and V: 400 * 8 * 128 * 2 B
    assert b == 2 * 2 * 48 * 128 * 2 + 2 * 400 * 8 * 128 * 2 == 1_687_552


def test_roofline_time_names_its_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = flops.roofline_time(197e12, 1.0, pk)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = flops.roofline_time(1.0, 819e9, pk)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
