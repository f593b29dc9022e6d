from learning_at_home_tpu.utils.nested import nested_flatten, nested_pack
from learning_at_home_tpu.utils.serialization import (
    pack_message,
    unpack_message,
    send_frame,
    recv_frame,
)
from learning_at_home_tpu.utils.asyncio_utils import (
    BackgroundLoop,
    run_in_background,
)
from learning_at_home_tpu.utils.timed_storage import TimedStorage, get_dht_time

__all__ = [
    "nested_flatten",
    "nested_pack",
    "pack_message",
    "unpack_message",
    "send_frame",
    "recv_frame",
    "BackgroundLoop",
    "run_in_background",
    "TimedStorage",
    "get_dht_time",
]
