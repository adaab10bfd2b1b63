#!/usr/bin/env python
"""Deep autoencoder (reference ``example/autoencoder/``: stacked
encoder-decoder trained on reconstruction loss, the unsupervised
pattern).  Tied task: 16x16 images that live on a 3-dim latent
manifold; the 3-unit bottleneck must reconstruct far better than the
best LINEAR rank-3 control (PCA with the same latent budget), proving
the nonlinear code learned the manifold.

    python examples/autoencoder/autoencoder.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def get_symbol(bottleneck=3):
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=64, name="enc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=bottleneck, name="enc2")
    h = mx.sym.FullyConnected(h, num_hidden=64, name="dec1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=256, name="dec2")
    # reconstruction target = the input itself (label slot)
    return mx.sym.LinearRegressionOutput(h, name="recon")


def synth(n, rs):
    """Images = blob at (cx, cy) with radius r — a 3-dim manifold."""
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
                           batch_size=64)
    mod = mx.mod.Module(get_symbol(), label_names=("recon_label",),
                        context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 3e-3},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.MSE())

    # reconstruction error vs the best rank-3 LINEAR baseline (PCA)
    mod.forward(mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(X)]),
                is_train=False)
    rec = mod.get_outputs()[0].asnumpy()
    ae_mse = float(((rec - X) ** 2).mean())
    Xc = X - X.mean(0)
    _u, s, vt = np.linalg.svd(Xc, full_matrices=False)
    pca3 = Xc @ vt[:3].T @ vt[:3] + X.mean(0)
    pca_mse = float(((pca3 - X) ** 2).mean())
    print("AE(3) mse %.5f | PCA(3) mse %.5f | ratio %.2f"
          % (ae_mse, pca_mse, ae_mse / pca_mse))
    return ae_mse, pca_mse


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-examples", type=int, default=1024)
    p.add_argument("--num-epochs", type=int, default=30)
    main(p.parse_args())
