"""Dense SwiGLU FFN: w2(SiLU(x w1) * (x w3)); FLOPs are its three weights."""
import jax

from bench.layers import _lin


def forward(p, x, spec, *, eps, low):
    h = jax.nn.silu(_lin(x, p["w1"], low)) * _lin(x, p["w3"], low)
    return _lin(h, p["w2"], low)


def matmul_params(spec, d_model):
    return 3 * d_model * spec["d_ff"]
