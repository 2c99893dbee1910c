// The user's kernels: Rodinia 3.1 opencl/bfs/Kernels.cl (BFS_1, BFS_2) as they stand, with three departures that upstream's API forces: Node {int starting; int no_of_edges;} is two int arrays (a ClArray holds one primitive type); *g_over is g_over[0]; both kernels of one compute take the same parameter list (the union of the source's two).
__kernel void BFS_1(__global int* g_starting, __global int* g_no_of_edges, __global int* g_graph_edges,
                    __global char* g_graph_mask, __global char* g_updating_graph_mask,
                    __global char* g_graph_visited, __global int* g_cost, __global char* g_over,
                    int no_of_nodes) {
    int tid = get_global_id(0);
    if (tid < no_of_nodes && g_graph_mask[tid]) {
        g_graph_mask[tid] = false;
        for (int i = g_starting[tid]; i < (g_no_of_edges[tid] + g_starting[tid]); i++) {
            int id = g_graph_edges[i];
            if (!g_graph_visited[id]) {
                g_cost[id] = g_cost[tid] + 1;
                g_updating_graph_mask[id] = true;
            }
        }
    }
}

__kernel void BFS_2(__global int* g_starting, __global int* g_no_of_edges, __global int* g_graph_edges,
                    __global char* g_graph_mask, __global char* g_updating_graph_mask,
                    __global char* g_graph_visited, __global int* g_cost, __global char* g_over,
                    int no_of_nodes) {
    int tid = get_global_id(0);
    if (tid < no_of_nodes && g_updating_graph_mask[tid]) {
        g_graph_mask[tid] = true;
        g_graph_visited[tid] = true;
        g_over[0] = true;
        g_updating_graph_mask[tid] = false;
    }
}
