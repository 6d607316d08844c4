"""gRPC service/client stubs for the Dispatcher contract.

Hand-written equivalent of what ``grpc_tools.protoc``'s python-grpc plugin
would generate from ``backtesting.proto`` (the plugin is not available in
this environment; only message codegen is). The ``.proto`` file remains the
single source of truth for the wire contract — this module only binds the
five unary RPCs to the generated message classes, once, in one place.

Compression is server->worker only, as in the reference (reference
``src/server/main.rs:212`` / ``src/worker/main.rs:49``): the dispatcher
server gzips its replies (first-delivery OHLCV panels on ``RequestJobs`` and
``FetchPayload``), and the channels built from
:func:`default_channel_options` send requests uncompressed. The worker's
bulk request is ``CompleteJobs``, whose DBXM result blocks deflate (zlib
level 6, one CPU core) at about 19 MB/s for a size ratio of 0.756; a TPU v5e
host's report moved ~20 MB/s with gzip on. gzip there saves a quarter of the
bytes and costs more time than sending them on any link faster than about
40 Mbit/s. A client that still gzips its requests keeps working, because
gRPC decodes each message by its own ``grpc-encoding``. Neither the channel
option nor ``insecure_channel(compression=...)`` can be undone per call, so
a channel that must stay plain sets neither.

Distributed-trace propagation rides IN the messages (``JobSpec.trace_id``
/ ``parent_span_id``, ``CompleteItem.trace_id``), not in gRPC metadata:
this hand-written stub layer registers plain unary handlers with no
interceptor chain, the worker's native channel codec re-serializes the
same protos across the compute boundary, and the journal persists them —
one carrier, visible to dbxlint's proto-drift rule, instead of a metadata
side-channel each hop would have to re-implement.
"""

from __future__ import annotations

import grpc

from . import backtesting_pb2 as pb

SERVICE_NAME = "dbx.rpc.Dispatcher"

# (method, request class, reply class) — mirrors the service block in
# backtesting.proto.
_METHODS = (
    ("RequestJobs", pb.JobsRequest, pb.JobsReply),
    ("SendStatus", pb.StatusRequest, pb.Ack),
    ("CompleteJob", pb.CompleteRequest, pb.Ack),
    ("CompleteJobs", pb.CompleteBatch, pb.CompleteBatchReply),
    ("GetStats", pb.StatsRequest, pb.StatsReply),
    ("FetchPayload", pb.PayloadRequest, pb.PayloadReply),
    ("AppendBars", pb.AppendRequest, pb.AppendReply),
    ("FetchCompiled", pb.CompiledRequest, pb.CompiledReply),
    ("OfferCompiled", pb.CompiledOffer, pb.Ack),
    ("TriggerDump", pb.DumpRequest, pb.DumpReply),
)

# Server-streaming RPCs (the live signal fan-out's Subscribe): the
# handler is a GENERATOR that yields replies for the stream's lifetime,
# so it occupies one server thread-pool slot per live subscriber
# connection — size DispatcherServer(max_workers=...) for the expected
# connection count plus unary headroom (one connection can carry many
# interests; see SubscribeRequest).
_STREAM_METHODS = (
    ("Subscribe", pb.SubscribeRequest, pb.PushUpdate),
)


class DispatcherServicer:
    """Interface for the server side; subclass and override each RPC."""

    def RequestJobs(self, request: pb.JobsRequest, context) -> pb.JobsReply:
        raise NotImplementedError

    def SendStatus(self, request: pb.StatusRequest, context) -> pb.Ack:
        raise NotImplementedError

    def CompleteJob(self, request: pb.CompleteRequest, context) -> pb.Ack:
        raise NotImplementedError

    def CompleteJobs(self, request: pb.CompleteBatch,
                     context) -> pb.CompleteBatchReply:
        raise NotImplementedError

    def GetStats(self, request: pb.StatsRequest, context) -> pb.StatsReply:
        raise NotImplementedError

    def FetchPayload(self, request: pb.PayloadRequest,
                     context) -> pb.PayloadReply:
        raise NotImplementedError

    def AppendBars(self, request: pb.AppendRequest,
                   context) -> pb.AppendReply:
        raise NotImplementedError

    def FetchCompiled(self, request: pb.CompiledRequest,
                      context) -> pb.CompiledReply:
        raise NotImplementedError

    def OfferCompiled(self, request: pb.CompiledOffer,
                      context) -> pb.Ack:
        raise NotImplementedError

    def Subscribe(self, request: pb.SubscribeRequest, context):
        """Server-streaming: yields :class:`pb.PushUpdate` messages."""
        raise NotImplementedError


def add_dispatcher_to_server(servicer: DispatcherServicer, server) -> None:
    """Register the servicer's unary + server-streaming handlers."""
    handlers = {
        name: grpc.unary_unary_rpc_method_handler(
            getattr(servicer, name),
            request_deserializer=req.FromString,
            response_serializer=rep.SerializeToString,
        )
        for name, req, rep in _METHODS
    }
    handlers.update({
        name: grpc.unary_stream_rpc_method_handler(
            getattr(servicer, name),
            request_deserializer=req.FromString,
            response_serializer=rep.SerializeToString,
        )
        for name, req, rep in _STREAM_METHODS
    })
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE_NAME, handlers),))


class DispatcherStub:
    """Client stub; one callable per RPC, bound to ``channel``.

    Streaming stubs (``Subscribe``) return an iterator of replies; the
    call stays open until the client drops it (``.cancel()`` / channel
    close) or the server ends the stream."""

    def __init__(self, channel: grpc.Channel):
        for name, req, rep in _METHODS:
            setattr(self, name, channel.unary_unary(
                f"/{SERVICE_NAME}/{name}",
                request_serializer=req.SerializeToString,
                response_deserializer=rep.FromString,
            ))
        for name, req, rep in _STREAM_METHODS:
            setattr(self, name, channel.unary_stream(
                f"/{SERVICE_NAME}/{name}",
                request_serializer=req.SerializeToString,
                response_deserializer=rep.FromString,
            ))


def default_channel_options() -> list[tuple[str, object]]:
    """Channel/server options: generous message sizes for OHLCV blocks, and
    no compression (see the module docstring)."""
    return [
        ("grpc.max_send_message_length", 256 * 1024 * 1024),
        ("grpc.max_receive_message_length", 256 * 1024 * 1024),
    ]
