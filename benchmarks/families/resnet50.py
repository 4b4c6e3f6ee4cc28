"""ResNet-50 through the zoo and the public `fit()`: what the `fit` driver
needs, built from a configuration file's sizes and `--seed`."""
from __future__ import annotations

from benchmarks.harness import work


class Built:
    def __init__(self, config, seed):
        from deeplearning4j_tpu.models.zoo import ResNet50
        from deeplearning4j_tpu.nn.updaters import Nesterovs

        m, a = config["model"], config["assumed"]
        self.config = config
        self.seed = int(seed)
        self.batch = int(a["batch_per_chip"])
        self.shape = (int(m["image_size"]), int(m["image_size"]),
                      int(m["channels"]))
        self.classes = int(m["num_classes"])
        upd = a["updater"]
        if upd["name"] != "Nesterovs":
            raise ValueError(f"unknown updater {upd['name']!r}")
        self.net = ResNet50(
            numClasses=self.classes, seed=self.seed & 0x7FFFFFFF,
            dataType=m["dataType"], inputShape=self.shape,
            updater=Nesterovs(upd["learning_rate"], upd["momentum"])).init()

    def make_pool(self, n):
        """`n` seeded batches made on the device in one jitted call:
        float32 images uniform in [0, 1) and one-hot float32 labels, the
        types a host iterator would hand `fit()`."""
        import jax
        import jax.numpy as jnp

        def make(key):
            kx, ky = jax.random.split(key)
            x = jax.random.uniform(kx, (n, self.batch) + self.shape,
                                   jnp.float32)
            y = jax.nn.one_hot(
                jax.random.randint(ky, (n, self.batch), 0, self.classes),
                self.classes, dtype=jnp.float32)
            return x, y

        key = jax.random.fold_in(jax.random.PRNGKey(self.seed & 0x7FFFFFFF),
                                 self.seed >> 31)
        x, y = jax.jit(make)(key)
        return [(x[i], y[i]) for i in range(n)]

    def train_flops_per_sample(self):
        h, w, c = self.shape
        return work.resnet50_train_flops(h, w, c, self.classes)

    def first_loss_range(self):
        """The first loss on random data is ln(classes) plus the L2 term
        (PR 22 read 10.69 at 1000 classes: 6.9 to 12)."""
        import math
        return math.log(self.classes) - 0.01, math.log(self.classes) + 5.1


def build(config, seed):
    return Built(config, seed)
