from .store import AsyncCheckpointer, CheckpointError, CheckpointStore
__all__ = ["AsyncCheckpointer", "CheckpointError", "CheckpointStore"]
