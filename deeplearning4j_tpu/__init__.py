"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A ground-up JAX/XLA/Pallas rebuild of the capability surface of the
Eclipse Deeplearning4j monorepo (reference: grzegorzgajda/deeplearning4j):
ND4J-style arrays (`ops`), the NeuralNetConfiguration builder DSL +
MultiLayerNetwork / ComputationGraph (`nn`), a SameDiff-equivalent graph
engine (`autodiff`), zoo models (`models`), distributed training over
`jax.sharding.Mesh` (`parallel`), data pipelines (`datasets`, `datavec`,
native C++ in `runtime`), evaluation (`eval`), and aux subsystems
(transfer learning, NLP, RL, hyperparameter search, UI stats).

Design notes: everything on the compute path is pure-functional and
jit-compiled as whole training steps (one XLA executable per step, donated
buffers); distribution is sharding annotations + compiler-inserted
collectives over ICI/DCN, not explicit messaging.
"""

__version__ = "0.1.0"


from deeplearning4j_tpu.ops import nd  # noqa: F401
