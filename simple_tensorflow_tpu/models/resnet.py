"""ResNet-50 v1.5 for ImageNet (BASELINE.json configs 2-3).

(ref: the reference targets "ResNet-50 ImageNet (DirectSession, single TPU
core via tf2xla)" and data-parallel over v4-32.)

TPU-first choices:
- NHWC layout + bf16 activations/weights with f32 matmul/conv accumulation
  (MXU-native); batch-norm statistics in f32.
- v1.5 variant (stride-2 in the 3x3 of the bottleneck) — the MLPerf
  reference config.
- Data-parallel: batch feed sharded over ('dp',), params replicated; XLA
  GSPMD inserts the gradient all-reduce (see stf.parallel).
"""

from __future__ import annotations

import numpy as np

import simple_tensorflow_tpu as stf

_BLOCKS = {  # per-stage bottleneck counts
    18: (2, 2, 2, 2),
    34: (3, 4, 6, 3),
    50: (3, 4, 6, 3),
    101: (3, 4, 23, 3),
    152: (3, 8, 36, 3),
}


def _conv(x, filters, ksize, stride, name):
    init = stf.init_ops.VarianceScaling(2.0, "fan_out", "truncated_normal")
    return stf.layers.conv2d(
        x, filters, ksize, strides=stride,
        padding="same", use_bias=False, kernel_initializer=init, name=name)


def _bn(x, training, name):
    return stf.layers.batch_normalization(
        x, momentum=0.9, epsilon=1e-5, training=training, fused=True,
        name=name)


def _bottleneck(x, filters, stride, training, projection, name):
    with stf.variable_scope(name):
        shortcut = x
        if projection:
            shortcut = _conv(x, 4 * filters, 1, stride, "proj_conv")
            shortcut = _bn(shortcut, training, "proj_bn")
        y = _conv(x, filters, 1, 1, "conv1")
        y = stf.nn.relu(_bn(y, training, "bn1"))
        y = _conv(y, filters, 3, stride, "conv2")  # v1.5: stride here
        y = stf.nn.relu(_bn(y, training, "bn2"))
        y = _conv(y, 4 * filters, 1, 1, "conv3")
        y = _bn(y, training, "bn3")
        return stf.nn.relu(y + shortcut)


def resnet_forward(x, num_classes=1000, depth=50, training=True,
                   recompute=False, conv0_space_to_depth=False):
    """Build the forward graph; x is NHWC.

    recompute=True rematerializes each residual block's activations in
    the backward pass (stf.recompute_grad / jax.checkpoint): cuts the
    dominant byte sink of the training step — saved block activations —
    at ~1.3x forward FLOPs, which ResNet can afford on v5e where the
    step is HBM-bandwidth-bound (an older chip run; ROADMAP S2).

    conv0_space_to_depth=True reformulates the stem (the MLPerf TPU
    recipe): space_to_depth(block 2) turns the 3-channel 224px input
    into 12 channels at 112px, and conv0 becomes a 4x4 stride-1 conv —
    mathematically an 8x8/s2 conv on the original image (a superset of
    the 7x7), exactly under VALID padding and modulo border handling
    under the SAME padding used here (the SAME pads land at different
    original-pixel offsets; train-from-scratch is unaffected, but do
    not expect bit-parity when resharding a pretrained 7x7 stem).
    The 3-channel conv is the MXU's worst case (channels pad to the
    128-lane width at <3% utilization); 12 channels quadruple that and
    drop the strided access pattern.
    """
    from . import common

    blocks = _BLOCKS[depth]
    with stf.variable_scope("resnet", reuse=stf.AUTO_REUSE):
        if conv0_space_to_depth:
            hh, ww = x.shape[1].value, x.shape[2].value
            if hh is None or ww is None or hh % 2 or ww % 2:
                raise ValueError(
                    f"conv0_space_to_depth needs even static spatial "
                    f"dims, got {hh}x{ww}")
            h = stf.space_to_depth(x, 2)        # [B, H/2, W/2, 12]
            h = _conv(h, 64, 4, 1, "conv0_s2d")  # ~ 8x8/s2 on the image
        else:
            h = _conv(x, 64, 7, 2, "conv0")
        h = stf.nn.relu(_bn(h, training, "bn0"))
        h = stf.layers.max_pooling2d(h, 3, 2, padding="same", name="pool0")
        block_idx = 0
        for stage, n_blocks in enumerate(blocks):
            filters = 64 * (2 ** stage)
            for i in range(n_blocks):
                stride = 2 if (stage > 0 and i == 0) else 1

                def block_fn(hh, _bi, filters=filters, stride=stride,
                             projection=(i == 0),
                             name=f"stage{stage}_block{i}"):
                    return _bottleneck(hh, filters, stride, training,
                                       projection=projection, name=name)

                h = common.maybe_recompute(block_fn, h, block_idx,
                                           recompute, "resnet_block")
                block_idx += 1
        h = stf.reduce_mean(h, axis=[1, 2], name="global_pool")  # NHWC pool
        h = stf.cast(h, stf.float32)
        logits = stf.layers.dense(
            h, num_classes,
            kernel_initializer=stf.init_ops.RandomNormal(stddev=0.01),
            name="fc")
    return logits


def resnet50_train_model(batch_size=64, image_size=224, num_classes=1000,
                         dtype=stf.bfloat16, learning_rate=0.1,
                         momentum=0.9, weight_decay=1e-4,
                         data_parallel=False, recompute=False,
                         conv0_space_to_depth=False):
    """Full training graph: images -> loss -> momentum-SGD update.

    With ``data_parallel`` and an active Mesh, the batch shards over 'dp'.
    """
    x = stf.placeholder(dtype, [batch_size, image_size, image_size, 3],
                        name="images")
    labels = stf.placeholder(stf.int32, [batch_size], name="labels")
    from ..framework import cost_model as _cm

    # recompute="auto": static per-chip activation estimate vs the
    # attached chip (framework/cost_model.py)
    _shards = _cm.mesh_shard_factor(["dp"] if data_parallel else [])
    recompute = _cm.resolve_recompute(
        recompute,
        _cm.resnet_activation_bytes(batch_size, image_size,
                                    dtype_bytes=dtype.size) / _shards,
        forward_flops=resnet_flops_per_image(50, image_size)
        * batch_size / _shards)
    if data_parallel:
        from simple_tensorflow_tpu import parallel

        mesh = parallel.current_mesh()
        if mesh is not None and "dp" in mesh.axis_names:
            parallel.shard_feed(x, "dp")
            parallel.shard_feed(labels, "dp")

    logits = resnet_forward(x, num_classes=num_classes, training=True,
                            recompute=recompute,
                            conv0_space_to_depth=conv0_space_to_depth)
    xent = stf.reduce_mean(stf.nn.sparse_softmax_cross_entropy_with_logits(
        labels=labels, logits=logits))
    # L2 on conv/fc kernels only (reference recipe: no BN params)
    l2 = [stf.nn.l2_loss(stf.cast(v._ref, stf.float32))
          for v in stf.trainable_variables()
          if "kernel" in v.var_name]
    loss = xent + weight_decay * stf.add_n(l2)
    gs = stf.train.get_or_create_global_step()
    opt = stf.train.MomentumOptimizer(learning_rate, momentum)
    train_op = opt.minimize(loss, global_step=gs)
    acc = stf.reduce_mean(stf.cast(
        stf.equal(stf.cast(stf.argmax(logits, 1, output_type=stf.int32),
                           stf.int32), labels), stf.float32))
    return {"images": x, "labels": labels, "logits": logits, "loss": loss,
            "train_op": train_op, "accuracy": acc, "global_step": gs}


def resnet_flops_per_image(depth=50, image_size=224, num_classes=1000):
    """Analytic fwd FLOPs/image (2*MACs); train step ~= 3x fwd."""
    # Reasonable standard value for ResNet-50 @224: ~4.1 GFLOP fwd.
    table = {50: 4.089e9, 18: 1.82e9, 34: 3.67e9, 101: 7.8e9, 152: 11.5e9}
    scale = (image_size / 224.0) ** 2
    return table[depth] * scale


def synthetic_imagenet(batch_size, image_size=224, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    images = rng.rand(batch_size, image_size, image_size, 3).astype(dtype)
    labels = rng.randint(0, 1000, size=batch_size).astype(np.int32)
    return images, labels
