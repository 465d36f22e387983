"""needs() against bytes and FLOPs worked by hand for one small shape."""

from benchmark.models import dlrm

CFG = {
    "num_dense": 3, "num_categorical": 2, "embed_dim": 4,
    "bottom_mlp": [8, 4], "top_mlp": [5, 1],
}


def test_score_and_train_by_hand():
    batch = 10
    # bottom 3x8 + 8x4 = 56 MACs; top input 4 + 3 pairs = 7: 7x5 + 5x1 = 40 MACs
    macs = 56 + 40
    params = (24 + 8) + (32 + 4) + (35 + 5) + (5 + 1)
    pair_flops = 3 * 2 * 4  # 3 pairs of 3 vectors, a multiply and an add each of 4
    flops = batch * (2 * macs + pair_flops)
    # 2 x 20 bits of indices fit 2 lanes; label + 3 dense lanes = 4
    wire = batch * (4 + 2) * 4
    rows = batch * 2 * 4 * 4
    score_bytes = rows + wire + params * 4 + batch * 4
    assert dlrm.needs(CFG, batch, "score") == {"flops": float(flops), "bytes": float(score_bytes)}
    train_bytes = score_bytes + 2 * rows + 2 * batch * 2 * 4 + 2 * params * 4
    assert dlrm.needs(CFG, batch, "train") == {
        "flops": float(3 * flops), "bytes": float(train_bytes)}


def test_interaction_width_matches_the_published_models():
    assert dlrm.interact_dim({"num_categorical": 26, "bottom_mlp": [512, 256, 128]}) == 479
    assert dlrm.interact_dim({"num_categorical": 26, "bottom_mlp": [512, 256, 64, 16]}) == 367
