import jax.numpy as jnp
import numpy as np

from bench import model


def test_table_redraw_matches_the_drawn_table(tiny, monkeypatch):
    _, cfg, _, _ = tiny
    monkeypatch.setattr(model, "CHUNK_BLOCKS", 4)   # several chunks, overlap
    rows = 128 * 10
    w = model.init_weights(cfg, rows, 2**33 + 7)
    mega = w["emb"]["mega"]
    key = model.table_key(2**33 + 7)
    assert float(model.table_change_sq(mega, key)) < 1e-9
    moved = mega.at[5].add(1.0).at[rows - 1].add(2.0)
    np.testing.assert_allclose(float(model.table_change_sq(moved, key)),
                               64 * (1 + 4), rtol=1e-5)
    assert abs(float(jnp.std(mega)) - 0.125) < 0.005


def test_weights_have_the_programs_shapes(tiny):
    from bench.drivers.train import Program
    from repro.core.dlrm import dlrm_param_specs
    from repro.nn.params import abstract_params
    _, cfg, tr, _ = tiny
    prog = Program(cfg, tr["batch"])
    want = abstract_params(dlrm_param_specs(prog.pcfg, prog.ebc))
    got = model.init_weights(cfg, prog.rows, 1)
    assert jax_shapes(got) == jax_shapes(want)
    assert model.leaf_names(got)[-1] == "emb.mega"
    assert len(model.leaf_names(got)) == len(model.flat_dense(got)) + 1


def jax_shapes(tree):
    import jax
    return jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)
