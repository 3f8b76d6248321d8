"""TPU-native serving stack.

End-to-end request path (contrast with reference ``main.py``, which
re-loads a pickle and runs sklearn inline per request):

    client ──HTTP──▶ server.py (asyncio HTTP/1.1, keep-alive)
      └─ asgi.py  App: route match, pydantic 422 validation
         └─ app.py /predict + /models/<id>/* handlers
            └─ scoring.py  ScorePath: coalesce concurrent rows into
               typed score units (or pool-worker dispatches)
               └─ engine.py InferenceEngine: padded bucket batch →
                  ONE jitted device call (argmax + max-softmax) →
                  futures resolved per request
"""

from mlapi_tpu.serving.app import build_app, feature_schema  # noqa: F401
from mlapi_tpu.serving.asgi import App, HTTPError, Request, Response  # noqa: F401
from mlapi_tpu.serving.engine import (  # noqa: F401
    InferenceEngine,
    TextClassificationEngine,
)
from mlapi_tpu.serving.registry import ModelRegistry, TenantLedger  # noqa: F401
from mlapi_tpu.serving.scoring import ScorePath  # noqa: F401
from mlapi_tpu.serving.router import Router, build_router_app  # noqa: F401
from mlapi_tpu.serving.server import Server  # noqa: F401
