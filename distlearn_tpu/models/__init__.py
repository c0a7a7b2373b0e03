"""Functional model zoo (the reference ships MNIST CNN + CIFAR convnet as
training-script-local model defs — examples/mnist.lua:53-81,
examples/Model.lua; here they are a first-class module)."""

from distlearn_tpu.models.core import Model, loss_fn, param_count
from distlearn_tpu.models.mnist_cnn import mnist_cnn
from distlearn_tpu.models.cifar_convnet import cifar_convnet
from distlearn_tpu.models.resnet import resnet, resnet50
from distlearn_tpu.models.transformer import (greedy_generate,
                                              transformer_lm)
from distlearn_tpu.models.hybrid import hybrid_lm

__all__ = ["Model", "loss_fn", "param_count", "mnist_cnn", "cifar_convnet",
           "resnet", "resnet50", "transformer_lm", "greedy_generate",
           "hybrid_lm"]
