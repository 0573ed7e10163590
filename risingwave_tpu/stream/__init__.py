from .message import (
    Barrier, BarrierKind, Watermark, Message,
    StopMutation, PauseMutation, ResumeMutation, ThrottleMutation,
    AddMutation, UpdateMutation,
)
from .executor import Executor, StatelessUnaryExecutor
from .project import ProjectExecutor, FilterExecutor
from .row_id import RowIdGenExecutor
from .materialize import MaterializeExecutor, ConflictBehavior
from .source import SourceExecutor
from .actor import Actor
from .exchange import (
    Channel, SimpleDispatcher, BroadcastDispatcher, HashDispatcher,
    ChannelInput, MergeExecutor, TapDispatcher,
)
from .hash_agg import HashAggExecutor
from .sorted_join import SortedJoinExecutor
from .sharded_join import ShardedSortedJoinExecutor
from .backfill import BackfillExecutor
from .sink import (SinkExecutor, BlackholeSink, FileSink, CallbackSink)
from .align import barrier_align
from .hop_window import HopWindowExecutor
from .dedup import AppendOnlyDedupExecutor
from .simple_agg import SimpleAggExecutor, StatelessSimpleAggExecutor
from .retract_top_n import RetractableTopNExecutor
from .sort import SortExecutor
from .misc import (
    ExpandExecutor, FlowControlExecutor, NoOpExecutor, UnionExecutor,
    ValuesExecutor, WatermarkFilterExecutor,
)
from .general_over_window import GeneralOverWindowExecutor, WindowSpec  # noqa: E402,F401
from .sharded_top_n import ShardedTopNExecutor  # noqa: E402,F401
from .sharded_over_window import ShardedOverWindowExecutor  # noqa: E402,F401
from .dynamic import DynamicFilterExecutor, NowExecutor  # noqa: E402,F401
from .project_set import ProjectSetExecutor  # noqa: E402,F401
