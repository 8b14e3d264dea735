"""Family `mlp`: N fully-connected layers of one width, ReLU between them,
softmax cross-entropy — the reference's benchmark model
(sw/mlp_mpi_example_f32.cpp).  Configuration keys: `layers`, `width`,
`compute_dtype`.

`program` is the only place that touches the system under test.  The batch,
the operation count and the plain reference are the benchmark's own.
"""

import jax
import jax.numpy as jnp

ITEM = "samples"
THROUGHPUT = "samples_per_s_per_chip"

# First step against the float32 reference below.
# Loss: the program multiplies in bf16 and takes the softmax in float32; ten
# layers of bf16 rounding move a loss of about 8.6 in the fourth digit.
LOSS_RTOL = 1e-2
# Gradient, relative L2 over the whole flat vector: bf16 rounds each
# activation and each backpropagated error to 8 bits (2^-9 relative), over
# 10 layers forward and 10 back.  Measured 4.6e-3 to 7.6e-3 on the chip at
# dp=1 over twelve seeds (PR 23); 2.6 times the worst is allowed, and an 8-bit
# float format (2^-4 relative) would miss it by a factor of five.
GRAD_TOL = 2e-2


def program(config: dict, job: dict):
    """(init(key) -> params, loss_fn(params, batch)) of the system under
    test, as examples/train_mlp.py builds them."""
    from fpga_ai_nic_tpu.models import mlp
    from fpga_ai_nic_tpu.utils.config import MLPConfig
    mcfg = MLPConfig(layer_sizes=(config["width"],) * (config["layers"] + 1),
                     dtype=config["compute_dtype"])
    return (lambda key: mlp.init(key, mcfg),
            lambda params, batch: mlp.loss_fn(params, batch, mcfg))


def global_batch(config: dict, job: dict) -> int:
    return job["batch_per_chip"] * job["dp"]


def items_per_step(config: dict, job: dict) -> int:
    return global_batch(config, job)


def make_batch(key, config: dict, job: dict):
    """(x [B, width] in the compute type, y [B] class ids), from the key."""
    kx, ky = jax.random.split(key)
    b, width = global_batch(config, job), config["width"]
    x = jax.random.normal(kx, (b, width), jnp.dtype(config["compute_dtype"]))
    y = jax.random.randint(ky, (b,), 0, width, jnp.int32)
    return x, y


def flops_per_item(config: dict, job: dict) -> float:
    """The reference's own accounting (sw/mlp_mpi_example_f32.cpp:794-798):
    forward, input-gradient and weight-gradient GEMMs, 2*C*C each; the first
    layer needs no input gradient.  Bias, ReLU and softmax count nothing."""
    c, n = config["width"], config["layers"]
    return 4.0 * c * c + (n - 1) * 6.0 * c * c


def reference_nll(params, batch, config: dict):
    """(summed negative log-likelihood, samples) of a block of samples: the
    same mathematics in plain float32 jax.numpy.  The equal layers between
    the first and the last run as one scanned body, which compiles once."""
    x, y = batch
    ws, bs = params["w"], params["b"]
    h = jnp.maximum(x.astype(jnp.float32) @ ws[0] + bs[0], 0.0)

    def layer(h, wb):
        return jnp.maximum(h @ wb[0] + wb[1], 0.0), None

    h, _ = jax.lax.scan(layer, h, (jnp.stack(ws[1:-1]), jnp.stack(bs[1:-1])))
    h = h @ ws[-1] + bs[-1]
    top = jnp.max(h, axis=-1, keepdims=True)
    logz = h - top - jnp.log(jnp.sum(jnp.exp(h - top), axis=-1,
                                     keepdims=True))
    nll = -jnp.take_along_axis(logz, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll), x.shape[0]
