"""PR-19 drive: declarative sharding subsystem + multimodal serving,
through PUBLIC exports only (docs/sharding.md, docs/serving.md
"Multimodal engines").

Forced-CPU 8-virtual-device recipe: run as
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python workspace/sharding_mm_drive.py
"""

import sys

sys.path.insert(0, "/root/repo")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import json  # noqa: E402
import threading  # noqa: E402
import urllib.request  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

# ---- 1. the rules table API ----------------------------------------------
from fengshen_tpu.sharding import (DEFAULT_LOGICAL_AXIS_RULES,  # noqa: E402
                                   resolve_spec, rules_fingerprint,
                                   use_rules, validate_rules)

validate_rules(DEFAULT_LOGICAL_AXIS_RULES)
assert resolve_spec(("embed", "heads")) == P("fsdp", "tensor")
assert resolve_spec(("batch", "seq", None)) == \
    P(("data", "fsdp"), "sequence", None)
fp_default = rules_fingerprint()
assert fp_default.startswith("lar1:")
custom = tuple((k, None) if k == "mlp" else (k, v)
               for k, v in DEFAULT_LOGICAL_AXIS_RULES)
with use_rules(custom):
    assert resolve_spec(("embed", "mlp")) == P("fsdp", None)
    assert rules_fingerprint() != fp_default
assert resolve_spec(("embed", "mlp")) == P("fsdp", "tensor")
try:
    validate_rules((("heads", "tenosr"),))
    raise SystemExit("typo table validated?!")
except ValueError:
    pass
print("[1] rules table API ok:", fp_default)

# ---- 2. sharded llama greedy decode token-identical ----------------------
from fengshen_tpu.models.llama import (LlamaConfig,  # noqa: E402
                                       LlamaForCausalLM)
from fengshen_tpu.parallel import (MeshConfig, make_mesh,  # noqa: E402
                                   make_shardings, set_mesh)
from fengshen_tpu.utils.generate import generate  # noqa: E402

assert len(jax.devices()) == 8, "need XLA_FLAGS device_count=8"
mesh = make_mesh(MeshConfig(data=2, fsdp=2, sequence=1, tensor=2))
set_mesh(mesh)
cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=2, num_attention_heads=4,
                  max_position_embeddings=48, dtype="float32")
model = LlamaForCausalLM(cfg)
ids = jnp.asarray(np.random.RandomState(0).randint(3, 127, (2, 8)))
params = model.init(jax.random.PRNGKey(0), ids)["params"]
ref = np.asarray(generate(model, params, ids, max_new_tokens=12,
                          eos_token_id=None, pad_token_id=0))
sharded = jax.device_put(params,
                         make_shardings(model.partition_rules(),
                                        params, mesh))
qk = sharded["model"]["layers_0"]["self_attn"]["q_proj"]["kernel"]
assert any(a is not None for a in qk.sharding.spec), "not sharded"
out = np.asarray(generate(model, sharded, ids, max_new_tokens=12,
                          eos_token_id=None, pad_token_id=0))
np.testing.assert_array_equal(out, ref)
print("[2] sharded llama greedy decode token-identical on 2x2x2 mesh")

# ---- 3. multimodal serving end-to-end ------------------------------------
from fengshen_tpu.api.main import (PipelineConfig,  # noqa: E402
                                   ServerConfig, build_stdlib_server)
from fengshen_tpu.pipelines.embedding import Pipeline  # noqa: E402
from fengshen_tpu.serving import create_multimodal_engine  # noqa: E402

pipe = Pipeline(small_test=True, seed=0)
eng = create_multimodal_engine("embedding", pipe,
                               {"max_batch": 2, "gather_ms": 2.0})
print("[3] embedding warmup:", round(eng.warmup(), 2), "s")
eng.start()
server = build_stdlib_server(
    ServerConfig(host="127.0.0.1", port=0, engine="embedding"),
    PipelineConfig(task="embedding"), pipeline=pipe, engine=eng)
port = server.server_address[1]
threading.Thread(target=server.serve_forever, daemon=True).start()
try:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/embedding",
        data=json.dumps({"input_text": "今天天气真好"}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        body = json.loads(r.read())
    assert body["engine_type"] == "embedding"
    emb = body["result"]["embedding"]
    assert abs(sum(x * x for x in emb) - 1.0) < 1e-3
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/stats",
                                timeout=10) as r:
        stats = json.loads(r.read())
    assert stats["engine_type"] == "embedding"
    assert stats["requests_total"] >= 1
    print("[3] embedding served over HTTP: dim", body["result"]["dim"],
          "| stats", {k: stats[k] for k in ("engine_type",
                                            "batches_total",
                                            "avg_batch")})
finally:
    server.shutdown()
    eng.stop()

print("DRIVE OK")
