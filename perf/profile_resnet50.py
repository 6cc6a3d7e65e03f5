import numpy as np
import paddle_tpu as paddle
paddle.device.use_compile_cache()
import paddle_tpu.nn.functional as F
from paddle_tpu import optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.vision.models import resnet50
import jax

def fence(t): np.asarray(t._data if hasattr(t, "_data") else t)

B, HW = 128, 224
rng = np.random.default_rng(0)
model = resnet50(num_classes=1000)
opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                         parameters=model.parameters())
def loss_fn(m, xb, yb):
    return F.cross_entropy(m(xb), yb).mean()
step = TrainStep(model, loss_fn, opt, amp_level="O2", amp_dtype="bfloat16")
x = paddle.to_tensor(rng.standard_normal((B, 3, HW, HW)).astype(np.float32))
y = paddle.to_tensor(rng.integers(0, 1000, size=(B,)).astype(np.int64))
for _ in range(3):
    loss = step(x, y)
fence(loss)
with jax.profiler.trace("/tmp/jaxtrace"):
    for _ in range(5):
        loss = step(x, y)
    fence(loss)
print("trace captured")
import subprocess
print(subprocess.run(["find", "/tmp/jaxtrace", "-name", "*.pb*", "-o", "-name", "*.json*"],
                     capture_output=True, text=True).stdout)
