#!/bin/bash
# variant 7: the TPU-native flagship (ResNet-50 / CIFAR-10 on a TPU)
python scripts/7.jax_tpu.py "$@"
