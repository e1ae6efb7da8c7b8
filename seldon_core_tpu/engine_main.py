"""Engine CLI: boot a GraphExecutor from a predictor spec and serve.

Counterpart of the engine Spring Boot app (reference:
engine/src/main/java/io/seldon/engine/App.java:39-107): the graph comes
from the ``ENGINE_PREDICTOR`` env var (base64 JSON PredictorSpec —
reference: EnginePredictor.java:58-108) or a ``--spec`` JSON file; serves
external REST on :8000 and gRPC on :5001 (same defaults as the reference).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import signal

from .graph.service import EngineApp
from .graph.spec import PredictorSpec, default_predictor, validate_predictor


def main(argv=None) -> None:
    parser = argparse.ArgumentParser("seldon-tpu-engine")
    parser.add_argument("--spec", help="path to predictor spec JSON (else ENGINE_PREDICTOR b64 env)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--http-port", type=int, default=int(os.environ.get("ENGINE_SERVER_PORT", 8000)))
    parser.add_argument("--grpc-port", type=int, default=int(os.environ.get("ENGINE_SERVER_GRPC_PORT", 5001)))
    parser.add_argument("--no-grpc", action="store_true")
    parser.add_argument("--log-level", default=os.environ.get("SELDON_LOG_LEVEL", "INFO"))
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=args.log_level.upper(),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )

    from .tracing import init_tracer

    init_tracer("seldon-tpu-engine")  # enabled iff TRACING env set

    if args.spec:
        with open(args.spec) as f:
            spec = PredictorSpec.from_dict(json.load(f))
    elif os.environ.get("ENGINE_PREDICTOR"):
        spec = PredictorSpec.from_env_b64(os.environ["ENGINE_PREDICTOR"])
    else:
        raise SystemExit("no graph: pass --spec or set ENGINE_PREDICTOR")
    spec = default_predictor(spec)
    validate_predictor(spec)

    from .graph.service import RequestLogger

    mesh = None
    if spec.tpu_mesh:
        # standalone engine process: the mesh spans this host's own devices
        from .parallel import make_mesh

        mesh = make_mesh(spec.tpu_mesh)
    app = EngineApp(spec, request_logger=RequestLogger.from_env(), mesh=mesh)
    # SIGTERM (the control plane's and Kubernetes' stop signal) ends the
    # server the way Ctrl-C does: exit code 0, not death by signal
    signal.signal(signal.SIGTERM, signal.default_int_handler)
    try:
        asyncio.run(app.serve(args.host, args.http_port, None if args.no_grpc else args.grpc_port))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
