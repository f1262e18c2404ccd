"""grace-tpu model zoo: functional models for the BASELINE.json configs.

Each model module exposes ``init(key, ...) -> (params, state)`` and
``apply(params, state, x, *, train) -> (out, new_state)`` — params/state are
plain pytrees, so GRACE memory state mirrors them leaf-for-leaf and
checkpoints with orbax alongside them.

* ``lenet``         — MNIST CNN (reference examples/torch/pytorch_mnist.py:73-89)
* ``resnet_cifar``  — cifar10-fast DAWNBench net (examples/dist/CIFAR10-dawndist/dawn.py:60-97)
* ``resnet``        — ResNet-50/101/152 v1.5 (torchvision stand-in used by
                      examples/torch/pytorch_synthetic_benchmark.py:49)
* ``transformer``   — BERT-style encoder (BASELINE.json BERT/PowerSGD config)
* ``lfm2``          — LFM2-MoE causal decoder: gated short convolutions,
                      grouped-query attention, sparse experts of which a chip
                      holds a share, next-token loss; ``(params, model_state,
                      ids) -> (loss, model_state)`` (the benchmark's
                      ``lfm2-24b-a2b-ep8`` configuration)
* ``deepseek_v3``   — DeepSeek-V3-shaped causal decoder: multi-head latent
                      attention (one latent and one shared rotary key for
                      all heads), shared experts beside routed ones of which
                      a chip holds a share (the expert layer, the head and
                      the loss are ``lfm2``'s); the benchmark's
                      ``kanana-2-30b-a3b-ep16`` configuration
* ``sdar``          — SDAR block-diffusion decoder (a Qwen3-shaped expert
                      decoder): the noised and the clean copy of a sequence
                      side by side under a block mask, position ids, a
                      softmax router, the masked tokens' weighted loss with
                      the step's noise drawn on the device (attention, the
                      expert walk and the head are ``lfm2``'s); the
                      benchmark's ``sdar-30b-a3b-ep8`` configuration
* ``smallthinker``  — SmallThinker causal decoder: windowed rotary layers
                      beside full-attention layers without positions (a
                      layer's mask and whether it rotates are the config's
                      two layouts), a softmax router that reads the layer's
                      input before attention runs, ReLU-gated experts
                      (attention, the expert walk and the head are
                      ``lfm2``'s, the router ``sdar``'s); the benchmark's
                      ``smallthinker-21b-a3b-ep8`` configuration
* ``qwen3_next``    — Qwen3-Next causal decoder: gated delta-rule layers
                      (the recurrence in chunks: products within a chunk,
                      one scan over the chunks that carries the float32
                      state), three in four, beside softmax attention with
                      an output gate at heads of 256 whose first 64 numbers
                      are rotated; 512 experts ten a token beside a gated
                      shared expert (attention, the expert walk and the
                      head are ``lfm2``'s, the router ``sdar``'s); the
                      benchmark's ``qwen3-next-80b-a3b-ep32`` configuration
* ``vgg``           — VGG-11/13/16/19 (the communication-bound classic of the
                      reference's synthetic-benchmark model list)
"""

from grace_tpu.models import (deepseek_v3, layers, lenet, lfm2, qwen3_next,
                              resnet, resnet_cifar, sdar, smallthinker,
                              transformer, vgg)

__all__ = ["deepseek_v3", "layers", "lenet", "lfm2", "qwen3_next", "resnet",
           "resnet_cifar", "sdar", "smallthinker", "transformer", "vgg"]
