"""Operations and bytes from shapes agree with the program's own counts."""
import jax
import pytest

from bench import counts, model


@pytest.mark.parametrize("name", ["internlm2-1.8b-serve", "olmo-1b-train"])
def test_param_count_matches_the_program(name):
    from repro.models.transformer import init_params

    cfg = model.model_config(model.load_config(name))
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n_tree = sum(x.size for x in jax.tree.leaves(tree))
    assert counts.param_count(cfg) == n_tree
    # ModelConfig.param_count counts two norm vectors per layer even when
    # the norm has no parameters, and leaves out the final norm's scale
    d, L = cfg.d_model, cfg.n_layers
    if cfg.norm == "nonparametric":
        assert cfg.param_count() == n_tree + 2 * d * L
    else:
        assert cfg.param_count() == n_tree - d


def test_flops_follow_the_6n_rule():
    cfg = model.model_config(model.load_config("olmo-1b-train"))
    n_mm = cfg.n_layers * counts.layer_matmul_params(cfg) + cfg.d_model * cfg.vocab
    attn = 3 * 4 * cfg.n_layers * cfg.n_heads * cfg.head_dim * (2048 + 1) / 2 * 2048
    assert counts.train_flops(cfg, 1, 2048) == pytest.approx(6 * n_mm * 2048 + attn)


def test_paged_attention_bytes_and_flops():
    cfg = model.model_config(model.load_config("internlm2-1.8b-serve"))
    page_bytes = 16 * cfg.n_kv_heads * cfg.head_dim * 2
    # contexts 1 and 17 need 1 and 2 pages of K and of V; q and out rows
    want = 2 * 3 * page_bytes + 2 * 2 * cfg.n_heads * cfg.head_dim * 2
    assert counts.paged_attention_bytes(cfg, [1, 17], 16) == want
    assert counts.paged_attention_flops(cfg, [1, 17]) == 4 * 16 * 128 * 18
