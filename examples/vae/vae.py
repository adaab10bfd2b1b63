#!/usr/bin/env python
"""Variational autoencoder (reference ``example/mxnet_adversarial_vae``
core, minus the GAN half): encoder -> (mu, logvar), reparameterized
sampling INSIDE the symbolic graph (``random_normal`` source op), KL
regularizer attached via ``MakeLoss``, reconstruction head.

The patterns this proves: stochastic nodes in a training graph (the
reparameterization trick), multi-head loss (recon + KL) through
``sym.Group``, and generation by binding the DECODER subgraph alone on
prior samples with the trained weights.

    python examples/vae/vae.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def decoder(z, out_dim, prefix="dec"):
    h = mx.sym.FullyConnected(z, num_hidden=64, name=prefix + "1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=out_dim, name=prefix + "2")
    return mx.sym.Activation(h, act_type="sigmoid", name=prefix + "_out")


def get_symbol(batch, latent, out_dim, kl_weight):
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=64, name="enc1")
    h = mx.sym.Activation(h, act_type="relu")
    mu = mx.sym.FullyConnected(h, num_hidden=latent, name="enc_mu")
    logvar = mx.sym.FullyConnected(h, num_hidden=latent,
                                   name="enc_logvar")
    eps = mx.sym.random_normal(loc=0.0, scale=1.0,
                               shape=(batch, latent))
    z = mu + mx.sym.exp(0.5 * logvar) * eps      # reparameterization
    recon = decoder(z, out_dim)
    recon_loss = mx.sym.LinearRegressionOutput(recon, name="recon")
    kl = -0.5 * mx.sym.sum(1 + logvar - mu * mu - mx.sym.exp(logvar))
    kl_loss = mx.sym.MakeLoss(kl * (kl_weight / batch), name="kl")
    return mx.sym.Group([recon_loss, kl_loss])


def synth(n, rs):
    """Blob images on a 3-dim manifold, in [0, 1]."""
    yy, xx = np.mgrid[0:16, 0:16]
    imgs = np.empty((n, 256), "float32")
    for i in range(n):
        cy, cx = rs.uniform(4, 12, 2)
        r = rs.uniform(2, 5)
        imgs[i] = np.exp(-(((yy - cy) ** 2 + (xx - cx) ** 2)
                           / (r * r))).ravel()
    return imgs


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    X = synth(args.num_examples, rs)
    it = mx.io.NDArrayIter({"data": X}, {"recon_label": X},
                           batch_size=args.batch_size)
    net = get_symbol(args.batch_size, args.latent, 256, args.kl_weight)
    mod = mx.mod.Module(net, label_names=("recon_label",),
                        context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 3e-3},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Loss())

    # reconstruction quality
    mod.forward(mx.io.DataBatch(
        [mx.nd.array(X[:args.batch_size])],
        [mx.nd.array(X[:args.batch_size])]), is_train=False)
    rec = mod.get_outputs()[0].asnumpy()
    mse = float(((rec - X[:args.batch_size]) ** 2).mean())

    # generation: bind the DECODER alone, feed prior samples with the
    # trained weights
    z = mx.sym.Variable("z")
    gen_sym = decoder(z, 256)
    gen = mx.mod.Module(gen_sym, data_names=("z",), label_names=(),
                        context=mx.current_context())
    gen.bind(data_shapes=[("z", (args.batch_size, args.latent))],
             for_training=False)
    arg_params, aux_params = mod.get_params()
    gen.set_params({k: v for k, v in arg_params.items()
                    if k.startswith("dec")}, aux_params,
                   allow_missing=True)
    zs = mx.nd.array(rs.randn(args.batch_size,
                              args.latent).astype("float32"))
    gen.forward(mx.io.DataBatch([zs], []), is_train=False)
    samples = gen.get_outputs()[0].asnumpy()
    # prior samples must look blob-like (bright peak, mostly-dark field)
    # and differ from one another (no posterior collapse)
    peak = float(samples.max(axis=1).mean())
    dark = float(np.median(samples))
    diversity = float(samples.std(axis=0).mean())
    print("recon mse %.5f | sample peak %.3f median %.3f "
          "diversity %.4f" % (mse, peak, dark, diversity))
    return mse, peak, dark, diversity


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-examples", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--latent", type=int, default=3)
    p.add_argument("--kl-weight", type=float, default=0.05)
    p.add_argument("--num-epochs", type=int, default=30)
    main(p.parse_args())
